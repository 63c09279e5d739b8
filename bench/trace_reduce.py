"""From the profiler's trace of the measured window to device numbers.

The reduction works on plain interval lists, so that it is checked on
synthetic events; ``load`` is the only part that knows the trace format
(``jax.profiler.ProfileData`` over the ``.xplane.pb`` file).

On a TPU plane, line "XLA Ops" holds one event per executed HLO op and
"XLA Modules" one per program run; a loop op's event spans the ops of its
body, so busy time is a union and an op's own time excludes what nests in
it. Line "Async XLA Ops" holds asynchronous ops (copies, collectives) from
start to done: they count as collective time where they are collectives,
and never as busy time. Host annotations (``TraceAnnotation``: the
harness's ``bench.build`` and the program's mirrored spans) share the
trace's clock.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_s, end_s)

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|ragged-all-to-all|"
    r"collective-permute|reduce-scatter|collective-broadcast)")

# Programs that only move or reshape data: JAX names an eager op's program
# after its primitive ("jit_reshape"). What runs in any other program is
# the density computation.
LAYOUT_PROGRAMS = frozenset({
    "reshape", "transpose", "dynamic_slice", "slice", "gather", "getitem",
    "concatenate", "copy", "convert_element_type", "broadcast_in_dim",
    "squeeze", "expand_dims", "device_put", "pad",
})


def op_name(text: str) -> str:
    """``%fusion.3 = f32[..] fusion(..), ..`` -> ``fusion.3``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def opcode(text: str) -> str:
    """The HLO opcode of an op event: ``%x = f32[4] all-gather(..)`` ->
    ``all-gather``; falls back to the name without its ``.N``."""
    if " = " in text:
        rhs = text.split(" = ", 1)[1]
        m = re.search(r"\s([a-z][a-z0-9-]*)\(", " " + rhs)
        if m:
            return m.group(1)
    return re.sub(r"\.\d+$", "", op_name(text))


def program_name(text: str) -> str:
    """``jit__pb_impl(5883991455540174240)`` -> ``_pb_impl``."""
    name = re.sub(r"\(\d+\)$", "", text)
    return name[4:] if name.startswith("jit_") else name


def is_collective(text: str) -> bool:
    return bool(_COLLECTIVE.match(opcode(text)))


def is_layout_program(text: str) -> bool:
    return program_name(text) in LAYOUT_PROGRAMS


# ------------------------------------------------------------- intervals
def clip(ivs: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in ivs if b > t0 and a < t1]


def union(ivs: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(ivs: Sequence[Interval]) -> float:
    return sum(b - a for a, b in union(ivs))


def gaps(ivs: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    """The parts of [t0, t1] that no interval covers."""
    out, at = [], t0
    for a, b in union(clip(ivs, t0, t1)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < t1:
        out.append((at, t1))
    return out


def self_times(events: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Own time per name of properly nested events: an event's duration
    less that of the events directly inside it. The sums add up to the
    union of all the events."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []           # [name, start, end, child_time]

    def close():
        name, a, b, child = stack.pop()
        out[name] += (b - a) - child
        if stack:
            stack[-1][3] += b - a

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            close()
        if stack and b > stack[-1][2]:   # overlap without nesting: trim
            b = stack[-1][2]
        stack.append([name, a, b, 0.0])
    while stack:
        close()
    return dict(out)


def innermost(spans: Sequence[Tuple[str, float, float]], t: float
              ) -> Optional[str]:
    """Name of the shortest span that covers time ``t``."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else None


def attribute_gaps(gap_ivs: Sequence[Interval],
                   spans: Sequence[Tuple[str, float, float]]
                   ) -> Dict[str, float]:
    """Idle seconds by the innermost host span open at each moment of the
    gaps; ``"(no span)"`` where none is. A gap is cut where a span opens
    or closes, so one gap can feed several names."""
    out: Dict[str, float] = defaultdict(float)
    for a, b in gap_ivs:
        near = [s for s in spans if s[2] > a and s[1] < b]
        cuts = sorted({a, b} | {t for _, s, e in near for t in (s, e)
                               if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            out[innermost(near, (lo + hi) / 2) or "(no span)"] += hi - lo
    return dict(out)


# ----------------------------------------------------------------- device
class Device:
    """One device's events inside the window, in seconds."""

    def __init__(self, name: str, ops, async_ops, programs):
        self.name = name
        self.ops = ops                  # [(text, start, end)] from XLA Ops
        self.async_ops = async_ops      # [(text, start, end)]
        self.programs = programs        # [(text, start, end)], by start
        self._starts = [a for _, a, _ in programs]

    def busy(self) -> List[Interval]:
        return union([(a, b) for _, a, b in self.ops])

    def busy_s(self) -> float:
        return total([(a, b) for _, a, b in self.ops])

    def program_of(self, t: float) -> Optional[str]:
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and self.programs[i][1] <= t <= self.programs[i][2]:
            return self.programs[i][0]
        return None

    def density_s(self) -> float:
        """Busy time in programs other than data movement."""
        return total([(a, b) for _, a, b in self.ops
                      if not is_layout_program(self.program_of(a) or "")])

    def collective_s(self) -> float:
        return total([(a, b) for text, a, b in self.ops + self.async_ops
                      if is_collective(text)])

    def op_self_s(self) -> Dict[str, float]:
        named = [(f"{program_name(self.program_of(a) or '?')}/"
                  f"{op_name(text)}", a, b) for text, a, b in self.ops]
        return self_times(named)


class Trace:
    def __init__(self, devices: List[Device], spans, t0: float, t1: float):
        self.devices = devices
        self.spans = spans              # [(name, start, end)] host spans
        self.t0, self.t1 = t0, t1

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def busy_s(self) -> float:
        """Mean over the devices of the seconds some op ran."""
        return sum(d.busy_s() for d in self.devices) / len(self.devices)

    def device_ops(self, top: int = 10) -> List[List]:
        acc: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for k, v in d.op_self_s().items():
                acc[k] += v / len(self.devices)
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))[:top]]

    def idle_gaps(self, top: int = 10) -> List[List]:
        acc: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for k, v in attribute_gaps(gaps(d.busy(), self.t0, self.t1),
                                       self.spans).items():
                acc[k] += v / len(self.devices)
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))[:top]]


def load(log_dir: str, window_span: str, device_ids: Sequence[int]) -> Trace:
    """Read the trace written under ``log_dir``. The window is the union
    of the host spans named ``window_span``; ``device_ids`` are the TPU
    ids whose planes are kept."""
    import jax

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    want = {f"/device:TPU:{i}" for i in device_ids}
    raw, spans = [], []
    for plane in data.planes:
        if plane.name in want:
            lines = {ln.name: ln for ln in plane.lines}

            def ev(name):
                ln = lines.get(name)
                return [] if ln is None else [
                    (e.name, e.start_ns / 1e9, (e.start_ns + e.duration_ns)
                     / 1e9) for e in ln.events]

            raw.append((plane.name, ev("XLA Ops"), ev("Async XLA Ops"),
                        ev("XLA Modules")))
        elif plane.name.startswith("/host:CPU"):
            # the host thread that ran the window: its annotations say
            # what the caller was doing
            for ln in plane.lines:
                evs = [(e.name, e.start_ns / 1e9,
                        (e.start_ns + e.duration_ns) / 1e9)
                       for e in ln.events if not e.name.startswith("$")]
                if any(n == window_span for n, _, _ in evs):
                    spans += evs
    if len(raw) != len(want):
        raise RuntimeError(f"trace holds {len(raw)} of the "
                           f"{len(want)} devices used")
    win = [(a, b) for n, a, b in spans if n == window_span]
    if not win:
        raise RuntimeError(f"no {window_span!r} span in the trace")
    t0, t1 = min(a for a, _ in win), max(b for _, b in win)
    devices = [Device(name, clip3(ops, t0, t1), clip3(aops, t0, t1),
                      sorted(clip3(progs, t0, t1), key=lambda e: e[1]))
               for name, ops, aops, progs in sorted(raw)]
    return Trace(devices, clip3(spans, t0, t1), t0, t1)


def clip3(events, t0: float, t1: float):
    return [(n, max(a, t0), min(b, t1)) for n, a, b in events
            if b > t0 and a < t1]
