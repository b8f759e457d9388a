"""Observability for the engine stack: the protocol flight recorder.

Three layers, all opt-in and all zero-cost when unused (see
docs/observability.md):

* :mod:`repro.obs.telemetry` — the on-device protocol counters pytree
  that ``run_batch(..., telemetry=True)`` threads through the scan
  carry (detections, votes, eliminations, tamper events, the paper's
  redundancy-overhead fraction), returned as ``BatchResult.telemetry``;
* :mod:`repro.obs.trace` — host span tracing (a context manager whose
  spans are also profiler annotations) with Chrome-trace JSON export;
* :mod:`repro.obs.metrics` — a process-wide counter/gauge registry
  with JSONL export.

:mod:`repro.obs.report` renders a ``BatchResult`` into the paper's
efficiency accounting (observed redundancy overhead vs the eq-2
closed form); :mod:`repro.obs.oblog` is the deduplicating warning
funnel the plan layer routes its fallback warnings through.

Layering: ``repro.obs`` sits BESIDE the engine stack, not above it —
nothing here imports ``repro.core.engine``/``engine_jax`` (the report
renderer duck-types ``BatchResult``), so the ``engineplan`` layer may
import it without violating the banned-import contract.
"""
from repro.obs import metrics, oblog, telemetry, trace  # noqa: F401
from repro.obs.metrics import REGISTRY  # noqa: F401
from repro.obs.oblog import reset_warn_once, warn_once  # noqa: F401
from repro.obs.telemetry import TEL_KEYS, Telemetry  # noqa: F401
from repro.obs.trace import TRACER, span  # noqa: F401
