"""In-scan observability plane for the fused serve loop.

Four pieces (see docs/observability.md):

- ``repro.obs.state`` — the struct-of-arrays contract: windowed int64
  telemetry channels (:class:`TeleState`), per-worker event rings
  (:class:`RingState`), and the frozen :class:`ObsParams` config.
- ``repro.obs.telemetry`` — the shared xp-generic tick update both
  backends evaluate (the NumPy host window / traced into the JAX scan) and
  the :class:`FleetObs` host recorder.
- ``repro.obs.export`` — Chrome trace-event / Perfetto JSON export and
  terminal summaries of the drained rings.
- ``repro.obs.profile`` — program tracing on the ``jax.profiler``
  clock: the ``profiled`` recorder, host ``span``s and device
  ``scope``s.
"""
from repro.obs.export import (format_ring_summary, format_tele_summary,
                              perfetto_trace, write_trace)
from repro.obs.profile import profiled, scope, span
from repro.obs.state import (EVENT_NAMES, OBS_MODES, RING_FIELDS,
                             TELE_FIELDS, ObsParams, RingState,
                             TeleState, init_ring, init_tele,
                             make_obs_params)
from repro.obs.telemetry import FleetObs, make_fleet_obs, obs_tick

__all__ = [
    "EVENT_NAMES", "OBS_MODES", "RING_FIELDS", "TELE_FIELDS",
    "ObsParams", "RingState", "TeleState", "FleetObs", "init_ring",
    "init_tele", "make_fleet_obs", "make_obs_params", "obs_tick",
    "perfetto_trace", "write_trace", "format_ring_summary",
    "format_tele_summary", "profiled", "scope", "span",
]
