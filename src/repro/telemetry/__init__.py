"""On-device observability for the engine stack.

Four layers (see ``docs/OBSERVABILITY.md``):

* :mod:`repro.telemetry.probes` -- jit-compatible fixed-shape state
  probes threaded through the engine scan carries (time-binned
  trajectories, counters, on-device latency histograms), plus the
  pure-Python :class:`PyProbes` twin and the host-side
  :func:`extract_probes` report.
* :mod:`repro.telemetry.trace` -- Chrome-trace/Perfetto ``trace_event``
  JSON export of request lifecycles and replan epochs.
* :mod:`repro.telemetry.manifest` -- schema-versioned ``RunRecord``
  JSONL provenance for every artifact-producing entry point.
* :mod:`repro.telemetry.spans` -- host spans and counters of the served
  path, recorded while a JAX profiler session records, on its clock.

``python -m repro.telemetry`` renders trajectory/SLI reports and
validates emitted trace/manifest files.
"""

from .manifest import (MANIFEST_SCHEMA_VERSION, append_record,
                       default_manifest_path, payload_digest, read_records,
                       run_record, validate_record)
from .probes import (PROBES, ProbeSpec, PyProbes, extract_probes,
                     hist_attainment, hist_edges, hist_percentile,
                     resolve_probe_spec)
from . import spans
from .timing import timeit_median
from .trace import (TRACE_SCHEMA_VERSION, lifecycle_events, replan_events,
                    trace_payload, validate_trace, write_trace)

__all__ = [
    "MANIFEST_SCHEMA_VERSION",
    "PROBES",
    "ProbeSpec",
    "PyProbes",
    "TRACE_SCHEMA_VERSION",
    "append_record",
    "default_manifest_path",
    "extract_probes",
    "hist_attainment",
    "hist_edges",
    "hist_percentile",
    "lifecycle_events",
    "payload_digest",
    "read_records",
    "replan_events",
    "resolve_probe_spec",
    "run_record",
    "spans",
    "timeit_median",
    "trace_payload",
    "validate_record",
    "validate_trace",
    "write_trace",
]
