"""The program's own spans and scopes in a profiler trace, reduced to
per-chunk host times, bytes moved and device time by pass.

The serve path marks itself (``repro.obs.profile``; docs/observability.md,
"Program spans"): host spans ``fleet.stream.*`` and ``fleet.serve.*``
carry integer stats (``chunk``, ``bytes``, ...), and the chunk
program's operations carry their ``fleet.*`` scope in the HLO ``op_name``
metadata. This reads them from the same ``.xplane.pb`` that
``trace_reduce`` reduces, on its clock and with its own-time rule
(:func:`trace_reduce.self_times`):

- ``spans``: each ``fleet.*`` and ``bench.*`` host span inside the
  window, with its stats and the device-busy time inside it;
- ``idle_gaps``: the gaps between device operations inside the window,
  each named by the innermost span of either prefix open at its middle;
- ``device_scopes``: device time inside the window by the innermost
  ``fleet.*`` scope of each operation (its own time, less what nests in
  it), ``(unscoped)`` for operations outside every scope.

The window is the ``bench.window`` span where the trace has one (a
benchmark run), else the stretch from the first ``fleet.stream.chunk``
to the last (``python -m repro.launch.fleet --stream --profile-dir``).
An operation's scope comes from the chunk program's optimized HLO text
(:func:`hlo_scopes`), matched by the operation's short name.

    python bench/program_trace.py <trace dir> <optimized HLO text> \\
        [--chunk-ticks 50 --dispatch-every 10]

prints the reduction and :func:`figures` as JSON.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import re

from trace_reduce import (DEVICE_PLANE, OP_LINE, WINDOW, Event, busy_before,
                          open_span, self_times, union, xplane_path)

PREFIXES = ("fleet.", "bench.")
CHUNK = "fleet.stream.chunk"
UNSCOPED = "(unscoped)"


@dataclasses.dataclass
class Span(Event):
    stats: dict = dataclasses.field(default_factory=dict)
    busy: float = 0.0  # ns of device-busy time inside the span


@dataclasses.dataclass
class ProgramTrace:
    window: Event
    busy_s: float
    spans: list[Span]
    idle_gaps: list[tuple[str, float]]  # (span name, seconds), longest first
    device_scopes: list[tuple[str, float]]  # (scope, seconds), largest first


def read_trace(path: str) -> tuple[dict[str, list[Event]], list[Span]]:
    """(device ops by device plane, host ``fleet.*``/``bench.*`` spans
    with their stats)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: dict[str, list[Event]] = {}
    spans: list[Span] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops += [Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Span(e.name, e.start_ns, e.end_ns,
                               dict(e.stats))
                          for e in line.events
                          if e.name.startswith(PREFIXES)]
    return devices, spans


def innermost_scope(op_path: str) -> str | None:
    """The last ``fleet.*`` component of an ``op_name`` path."""
    found = [c for c in op_path.split("/") if c.startswith("fleet.")]
    return found[-1] if found else None


_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([^\s,}]+)")


def hlo_scopes(text: str) -> dict[str, str]:
    """Instruction name -> innermost ``fleet.*`` scope, from an optimized
    HLO module's text. An instruction without a scope of its own that
    calls a computation (a fusion) takes the scope most of that
    computation's instructions carry."""
    own: dict[str, str | None] = {}
    calls: dict[str, str] = {}
    members: dict[str, list[str]] = collections.defaultdict(list)
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            if line.rstrip().endswith("{"):  # a computation's header
                comp = line.split()[1 if line.startswith("ENTRY") else 0]
                comp = comp.lstrip("%")
            continue
        name = m.group(1)
        o = _OP_NAME.search(line)
        own[name] = innermost_scope(o.group(1)) if o else None
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        if comp is not None:
            members[comp].append(name)
    out = {}
    for name, sc in own.items():
        if sc is None and name in calls:
            inner = collections.Counter(
                own[n] for n in members[calls[name]] if own[n])
            sc = inner.most_common(1)[0][0] if inner else None
        if sc is not None:
            out[name] = sc
    return out


def reduce(devices: dict[str, list[Event]], spans: list[Span],
           scopes: dict[str, str], top: int = 10) -> ProgramTrace | None:
    """None where the trace holds no window or no device operation."""
    windows = [s for s in spans if s.name == WINDOW]
    chunks = [s for s in spans if s.name == CHUNK]
    if windows:
        window = Event(WINDOW, windows[0].start, windows[0].end)
    elif chunks:
        window = Event(CHUNK, min(s.start for s in chunks),
                       max(s.end for s in chunks))
    else:
        return None
    if not any(devices.values()):
        return None
    w0, w1 = window.start, window.end
    inner = sorted((dataclasses.replace(s) for s in spans
                    if s.name != WINDOW and s.start >= w0 and s.end <= w1),
                   key=lambda s: s.start)
    by_scope: dict[str, float] = {}
    busy_total = 0.0
    gaps: list[tuple[str, float]] = []
    for ops in devices.values():
        busy = union([(max(o.start, w0), min(o.end, w1)) for o in ops
                      if o.end > w0 and o.start < w1])
        prefix = [0.0]
        for s, e in busy:
            prefix.append(prefix[-1] + e - s)
        busy_total += prefix[-1]
        for name, t in self_times(ops, w0, w1).items():
            sc = scopes.get(name, UNSCOPED)
            by_scope[sc] = by_scope.get(sc, 0.0) + t
        for sp in inner:
            sp.busy += (busy_before(busy, prefix, sp.end)
                        - busy_before(busy, prefix, sp.start)) / len(devices)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((open_span([window] + inner, (g0 + g1) / 2),
                             (g1 - g0) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    n = len(devices)
    return ProgramTrace(
        window=window, busy_s=busy_total / n * 1e-9, spans=inner,
        idle_gaps=gaps[:top],
        device_scopes=sorted(((k, v / n * 1e-9)
                              for k, v in by_scope.items()),
                             key=lambda kv: -kv[1]))


def _mean(xs):
    return sum(xs) / len(xs) if xs else None


def figures(r: ProgramTrace, chunk_ticks: int,
            dispatch_every: int) -> dict[str, float | None]:
    """Per-chunk means of the launch's host spans, the bytes that cross,
    and device time per dispatch tick and per tick, over the chunks of
    the window (None where the trace has no such span or scope)."""
    def named(name):
        return [s for s in r.spans if s.name == name]
    n_chunks = len(named("fleet.serve.upload"))
    scope_s = dict(r.device_scopes)
    dispatch_s = sum(v for k, v in scope_s.items()
                     if k == "fleet.dispatch"
                     or k.startswith("fleet.dispatch."))
    per = {}
    if n_chunks:
        per["dispatch_device_ms"] = (
            dispatch_s * 1e3 / (n_chunks * chunk_ticks / dispatch_every)
            if dispatch_s else None)
        per["tick_device_us"] = (
            scope_s["fleet.tick"] * 1e6 / (n_chunks * chunk_ticks)
            if "fleet.tick" in scope_s else None)
    return {
        "serve_upload_ms": _mean([(s.end - s.start) * 1e-6
                                  for s in named("fleet.serve.upload")]),
        "serve_readback_ms": _mean([(s.end - s.start - s.busy) * 1e-6
                                    for s in named("fleet.serve.readback")]),
        "serve_call_ms": _mean([(s.end - s.start) * 1e-6
                                for s in named("fleet.serve.call")]),
        "serve_transfer_mb": (
            sum(s.stats.get("bytes", 0) for s in r.spans
                if s.name in ("fleet.serve.upload", "fleet.serve.readback"))
            / n_chunks * 1e-6 if n_chunks else None),
        "dispatch_device_ms": per.get("dispatch_device_ms"),
        "tick_device_us": per.get("tick_device_us")}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("hlo", help="the chunk program's optimized HLO text")
    ap.add_argument("--chunk-ticks", type=int, default=50)
    ap.add_argument("--dispatch-every", type=int, default=10)
    args = ap.parse_args(argv)
    with open(args.hlo) as f:
        scopes = hlo_scopes(f.read())
    r = reduce(*read_trace(xplane_path(args.trace_dir)), scopes)
    if r is None:
        raise SystemExit("no window or no device operation in the trace")
    per_span = collections.defaultdict(list)
    for s in r.spans:
        per_span[s.name].append(((s.end - s.start) * 1e-6, s.busy * 1e-6))
    out = {"window_s": (r.window.end - r.window.start) * 1e-9,
           "busy_s": r.busy_s,
           "device_scopes": r.device_scopes,
           "idle_gaps": r.idle_gaps,
           "spans_ms": {k: {"n": len(v),
                            "mean": _mean([a for a, _ in v]),
                            "busy_mean": _mean([b for _, b in v])}
                        for k, v in sorted(per_span.items())},
           "figures": figures(r, args.chunk_ticks, args.dispatch_every)}
    print(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    main()
