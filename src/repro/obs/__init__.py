"""Dependency-free observability for the codesign stack.

Three small, stdlib-only modules, threaded through every hot path of the
sweep/serve/gateway system (see ``docs/observability.md``):

* :mod:`repro.obs.metrics` -- a process-wide registry of thread-safe
  counters, gauges, and fixed-bucket histograms with snapshot/reset
  semantics and two exporters (Prometheus text + canonical JSON). The
  gateway serves it at ``GET /v1/metrics``.
* :mod:`repro.obs.trace`   -- context-manager spans, each also a
  ``repro.<name>`` profiler annotation on the device trace's clock; inside
  a request trace they nest under a per-request trace id that rides the
  HTTP wire as an ``X-Repro-Trace`` header, and a ``"trace": true``
  request envelope field returns the span tree in the response.
* :mod:`repro.obs.logging` -- structured JSON line logging with a
  verbosity knob (the CLI ``serve --log-level`` flag).

Design rule: observability is **additive, never on the answer path**.
Untraced ``/v1/query`` responses stay byte-identical to the
pre-instrumentation ones. What tracing costs is measured by the
benchmark's traced against untraced runs (``bench/run.py --trace 0|1``).
"""

from .exemplar import ExemplarStore  # noqa: F401
from .logging import configure_logging, get_logger  # noqa: F401
from .slo import (  # noqa: F401
    DEFAULT_OBJECTIVES,
    SLOObjective,
    SLOTracker,
    bucket_quantile,
)
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Registry,
    get_registry,
)
from .trace import (  # noqa: F401
    TRACE_HEADER,
    Span,
    current_span,
    current_trace_id,
    new_trace_id,
    set_attrs,
    span,
    trace,
)
