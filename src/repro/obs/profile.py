"""Program tracing on the ``jax.profiler`` clock.

Three small tools, all writing into the one profiler trace (host spans
and device operations share its nanosecond clock):

- :func:`profiled` — context manager recording a ``jax.profiler`` trace
  of the enclosed block into a directory (viewable in Perfetto /
  TensorBoard); with no directory, or when jax is absent, a no-op, so
  callers wrap launches unconditionally (``--profile-dir``).
- :func:`span` — a host span (``jax.profiler.TraceAnnotation``); its
  keyword values become stats on the span's event, so a counter rides
  on the span that bounds its work. Near free while no trace records.
- :func:`scope` — a device scope (``jax.named_scope``) for the
  xp-generic passes: under ``jax.numpy`` it names the operations traced
  inside it (HLO ``op_name`` metadata only; the optimized program is
  otherwise the same), under NumPy it does nothing.

Every program span and scope is named ``fleet.*`` (docs/observability.md,
"Program spans").
"""
from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def profiled(trace_dir: str | None = None):
    """Record a ``jax.profiler`` trace of the enclosed block into
    ``trace_dir``: host spans and device operations, without the Python
    call tracer (no-op when ``trace_dir`` is falsy or jax is
    unavailable)."""
    if not trace_dir:
        yield
        return
    try:
        import jax
    except ImportError:  # profiler requested but no jax: still run
        yield
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # spans and device ops, no Python calls
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        yield


def span(name: str, **counts):
    """A host span ``name`` whose keyword values (ints) are recorded as
    stats of its trace event."""
    import jax
    return jax.profiler.TraceAnnotation(name, **counts)


def scope(name: str, xp):
    """``jax.named_scope(name)`` when ``xp`` is ``jax.numpy``; a null
    context under NumPy."""
    if xp is np:
        return contextlib.nullcontext()
    import jax
    return jax.named_scope(name)
