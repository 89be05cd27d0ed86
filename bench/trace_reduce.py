"""Reduction of a profiler trace to the benchmark's per-layer numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. On a TPU the device operations are the
events of the line ``XLA Ops`` on each plane ``/device:TPU:<n>``; the
benchmark's spans are host events named ``bench.*`` (written with
``jax.profiler.TraceAnnotation``). Both carry nanosecond times on one
clock.

Output (:class:`Reduced`):

- ``busy_s``: the union of device-operation intervals inside the
  ``bench.window`` span, averaged over the devices; ``window_s``: that
  span's length;
- ``spans``: each ``bench.*`` span with the device-busy time inside it;
- ``idle_gaps``: the gaps between device operations inside the window,
  each named by the innermost ``bench.*`` span open at its middle
  (``bench.window`` alone means the stream loop's own host code);
- ``device_ops``: device time inside the window by operation's short
  name, each less the time of the operations nested in it.

``python bench/trace_reduce.py <trace dir>`` prints the planes, lines and
a few event names of a trace, to check by hand what a chip writes.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import sys

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float  # ns


@dataclasses.dataclass
class Span(Event):
    busy: float = 0.0  # ns of device-busy time inside the span


@dataclasses.dataclass
class Reduced:
    window: Event
    window_s: float
    busy_s: float
    spans: list[Span]
    idle_gaps: list[tuple[str, float]]  # (span name, seconds), longest first
    device_ops: list[tuple[str, float]]  # (op name, seconds), largest first


def xplane_path(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_events(path: str) -> tuple[dict[str, list[Event]], list[Event]]:
    """(device ops by device plane, host ``bench.*`` spans)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops += [Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns, e.end_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return devices, spans


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_before(busy: list[tuple[float, float]], prefix: list[float],
                t: float) -> float:
    """Busy time before ``t``; ``prefix[i]`` is the busy time of the
    first ``i`` intervals of the disjoint, sorted ``busy``."""
    i = bisect.bisect_right(busy, (t, float("inf"))) - 1
    if i < 0:
        return 0.0
    s, e = busy[i]
    return prefix[i] + min(t, e) - s


def reduce(devices: dict[str, list[Event]], spans: list[Event],
           top: int = 10) -> Reduced | None:
    """None where the trace holds no window or no device operation."""
    windows = [s for s in spans if s.name == WINDOW]
    if not windows or not any(devices.values()):
        return None
    w0, w1 = windows[0].start, windows[0].end
    inner = sorted((s for s in spans if s.name != WINDOW
                    and s.start >= w0 and s.end <= w1),
                   key=lambda s: s.start)
    by_op: dict[str, float] = {}
    busy_per_device = []
    gaps: list[tuple[str, float]] = []
    out_spans = [Span(s.name, s.start, s.end) for s in inner]
    for ops in devices.values():
        busy = union([(max(o.start, w0), min(o.end, w1)) for o in ops
                      if o.end > w0 and o.start < w1])
        prefix = [0.0]
        for s, e in busy:
            prefix.append(prefix[-1] + e - s)
        busy_per_device.append(prefix[-1])
        for name, t in self_times(ops, w0, w1).items():
            by_op[name] = by_op.get(name, 0.0) + t
        for sp in out_spans:
            sp.busy += (busy_before(busy, prefix, sp.end)
                        - busy_before(busy, prefix, sp.start)) / len(devices)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((open_span(inner, (g0 + g1) / 2),
                             (g1 - g0) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    ops_sorted = sorted(by_op.items(), key=lambda kv: -kv[1])
    return Reduced(
        window=Event(WINDOW, w0, w1),
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy_per_device) / len(busy_per_device) * 1e-9,
        spans=out_spans,
        idle_gaps=gaps[:top],
        device_ops=[(n, t * 1e-9 / len(devices))
                    for n, t in ops_sorted[:top]])


def op_name(name: str) -> str:
    """An operation's short name: a TPU trace names each operation by its
    whole HLO instruction (``%while.285 = (u32[], ...) while(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(ops: list[Event], w0: float, w1: float) -> dict[str, float]:
    """Each operation's time inside ``[w0, w1]`` less that of the
    operations nested in it (a scan's ``while`` holds its body's
    operations on the same line), by short name."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self time]

    def close(entry):
        out[entry[0]] = out.get(entry[0], 0.0) + entry[2]

    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        s, e = max(o.start, w0), min(o.end, w1)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([op_name(o.name), e, e - s])
    for entry in stack:
        close(entry)
    return out


def open_span(spans: list[Event], t: float) -> str:
    """The innermost span open at ``t`` (the latest to start)."""
    name = WINDOW
    for s in spans:
        if s.start > t:
            break
        if s.end >= t:
            name = s.name
    return name


def reduce_dir(trace_dir: str) -> Reduced | None:
    return reduce(*read_events(xplane_path(trace_dir)))


def describe(trace_dir: str, per_line: int = 4) -> None:
    import jax
    pd = jax.profiler.ProfileData.from_file(xplane_path(trace_dir))
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events; "
                  f"{[e.name for e in evs[:per_line]]}")


if __name__ == "__main__":
    describe(sys.argv[1])
