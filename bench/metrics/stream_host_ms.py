"""Mean per chunk, in the traced stretch, of the host time of the stream
loop itself: the chunk's interval (its ``bench.take`` start to the next,
or to the window's end) less its ``bench.run_serve`` span. That is the
take, both per-chunk snapshots and the record."""


def read(run):
    t = run.trace
    if t is None:
        return None
    takes = [s for s in t.spans if s.name == "bench.take"]
    serves = [s for s in t.spans if s.name == "bench.run_serve"]
    if not takes or len(serves) != len(takes):
        return None
    ends = [s.start for s in takes[1:]] + [t.window.end]
    host = [(e - k.start) - (r.end - r.start)
            for k, r, e in zip(takes, serves, ends)]
    return sum(host) / len(host) * 1e-6
