"""Unified telemetry layer: metrics registry, span tracer, structured
logging, and the Prometheus exposition the service serves at /metrics.

The package is organized as:

- ``obs.metrics``  — Counter/Gauge/Histogram instruments + Registry +
  Prometheus text rendering. Hot-path increments are lock-free (GIL
  atomicity; a lost increment under a race is acceptable for stats,
  corruption is not possible). ``BABBLE_OBS=0`` is the kill switch: hot
  instruments become no-ops, zero-cost function-backed instruments keep
  working so ``get_stats`` and ``/metrics`` stay truthful.
- ``obs.trace``    — lightweight span tracer following one sync (and one
  transaction) through the pipeline; finished spans feed the
  ``sync_stage_seconds{stage=...}`` histograms and a bounded ring of
  recent traces served at ``/telemetry``.
- ``obs.telemetry``— NodeTelemetry: the per-node registry wiring every
  subsystem's counters into instruments, the legacy ``get_stats``
  compatibility snapshot, and the /metrics / /telemetry renderers.
- ``obs.catalog``  — the instrument catalog (name, type, labels,
  meaning): the single source of truth that registration, the docs
  table (docs/observability.md), and ``obs.lint`` all check against.
- ``obs.log``      — one logging entry point (level / JSON toggle /
  node-id correlation) replacing per-module ad-hoc setup.
- ``obs.lint``     — ``python -m babble_tpu.obs.lint``: fails when a
  cataloged instrument is missing from the docs table or vice versa.
- ``obs.profile``  — always-on ~50 Hz thread-stack sampler: stage-
  attributed collapsed stacks at ``GET /profile`` and the
  ``profile_stage_samples{stage}`` instrument.
- ``obs.healthview`` — ``python -m babble_tpu.obs.healthview``: merge
  every node's /metrics + /stats + /suspects into per-node lag,
  queue-depth, quarantine, and commit-p50-SLO scoring.
"""

from .metrics import Registry, enabled, set_enabled  # noqa: F401
