"""Unified telemetry layer (PR 9): metrics registry + request tracing.

Two host-side surfaces, both OFF by default and zero-allocation when
disabled, shared by the engine (``repro.serving``), the cluster
(``repro.cluster``) and the front end (``repro.frontend``):

- ``repro.obs.metrics`` — a process-wide registry of labeled Counters /
  Gauges / Histograms (fixed log-bucket latency histograms), with
  ``snapshot()`` for structured export and ``render()`` for
  Prometheus-style text exposition;
- ``repro.obs.trace`` — per-request lifecycle spans (queued →
  chunked-prefill slices → decode → suspend/migrate → finish/shed) and
  engine-step / cluster-tick events on the existing sim-clocks,
  recorded into a bounded ring and exported as Chrome trace-event JSON
  loadable in Perfetto; ``repro.obs.trace.span`` puts host spans on
  the JAX profiler's own clock instead, beside the device ops, whose
  ``jax.named_scope`` paths (``pam.*``, ``kv.*``, ``attn.*``,
  ``model.*``) say which part of the program each op belongs to.

Enable both for a run with::

    from repro import obs
    reg = obs.metrics.install(obs.metrics.MetricsRegistry())
    coll = obs.trace.install(obs.trace.TraceCollector())
    ...build engines / routers / servers, run...
    print(reg.render())          # Prometheus text
    coll.write("trace.json")     # load in https://ui.perfetto.dev

Instrumentation points bind to whatever registry/collector is installed
at CONSTRUCTION time (engines) or look the collector up per hook
(cheap module-global read), so installing before building the serving
stack is all that is needed. The fused-dispatch and donation
invariants are unaffected: every hook is host-side bookkeeping around
the existing per-step readbacks.
"""

from repro.obs import metrics, trace
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import TraceCollector

__all__ = ["metrics", "trace", "MetricsRegistry", "TraceCollector"]
