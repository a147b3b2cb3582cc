"""Unified telemetry plane shared by the mesh engine (Plane B), the event
simulator (Plane A) and the benchmark driver.

  * :mod:`repro.obs.registry` — ONE declarative metric schema: every mesh
    ``STAT_*`` counter slot and every simulator ``Counters`` field is
    declared here exactly once, with unit, kind, cross-plane mapping and
    paper-figure provenance.  ``core/dex.py`` derives its ``STAT_*``
    indices and ``N_STATS`` from it, so adding a counter can never
    silently alias an old slot.
  * :mod:`repro.obs.timeline` — per-batch phase-segmented wall-time
    instrumentation (``BatchTimeline``) wrapped around the mesh programs,
    with ``block_until_ready`` fencing and counter deltas piggybacked on
    the engine's existing psums (zero added collectives).
  * :mod:`repro.obs.trace` — Chrome trace-event JSON export of a timeline
    (viewable in Perfetto / chrome://tracing).
  * :mod:`repro.obs.drift` — the mesh-vs-sim counter comparison
    (``assert_plane_agreement``) with per-metric tolerances and a readable
    drift report, replacing the ad-hoc checks the mesh benchmarks used to
    hand-roll.

Import surface is kept light: only the registry (pure numpy) loads here;
timeline/trace/drift import jax lazily so Plane-A-only users never pay
for it.
"""

from repro.obs import registry  # noqa: F401  (the always-safe core)

__all__ = ["registry"]
