"""Unified telemetry for dlrover-tpu: span tracing, metrics, attribution.

Three layers, one spine (docs/observability.md):

- ``obs.trace`` — a low-overhead, thread-safe span tracer the trainer,
  prefetcher, checkpoint engine and grad-sync paths write the real step
  timeline into; exports Chrome trace-event JSON (Perfetto-loadable)
  and answers "what is this process doing RIGHT NOW" (hang
  attribution);
- ``obs.metrics`` — a counters/gauges/histograms registry with
  Prometheus text exposition; the existing ``PipelineStats`` record
  folds into it so there is exactly one export path for every number
  the fast-path subsystems produce;
- ``obs.aggregate`` — the master's side: per-worker step-time
  aggregation, straggler detection against the fleet median, hang
  reports enriched with each worker's last open span, and the fleet
  goodput rollup;
- ``obs.goodput`` — the accounting layer: a ``GoodputLedger`` that
  attributes every second of trainer wall time to a closed taxonomy
  derived from the span stream, with a closure invariant
  (``tests/test_goodput.py``; a running trainer's: ``tests/test_obs.py``);
- ``obs.flight_recorder`` — the forensics layer: an always-on black
  box that dumps a self-contained bundle (trace, metrics, stacks,
  events, manifest) on crash/hang/degraded-entry or master request,
  plus on-demand K-step ``jax.profiler`` captures.
"""
