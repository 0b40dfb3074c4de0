"""Transaction-lifecycle observability — spans, flight recorder, probes.

The reference leaned on BEAM tooling (observer's process/event views,
error_logger) for runtime forensics; this package rebuilds the two
halves natively for the TPU serving stack:

- :mod:`antidote_tpu.obs.spans` — a txid-correlated span tree across
  every plane (coordinator → log → device plane → inter-DC →
  dep-gate), held in a bounded in-process ring, queryable in tests and
  exportable as Chrome ``trace_event`` JSON (loadable in Perfetto
  alongside the JAX profiler captures :mod:`antidote_tpu.obs.prof`
  produces).
- :mod:`antidote_tpu.obs.events` — a per-subsystem flight recorder:
  bounded rings of structured events, dumped to disk automatically on
  txn aborts, error-monitor trips, and probe violations.
- :mod:`antidote_tpu.obs.probe` — online self-checks: the set_aw
  read-inclusion probe (chasing the VERDICT round-5 transient miss)
  and the ISSUE 7 causal-probe auditor (write→remote-read staleness +
  causal-order tripwire).
- :mod:`antidote_tpu.obs.prof` — the device-plane profiler (ISSUE 2):
  kernel spans over the jitted mat/ and interdc entry points,
  compile-cache-miss counters, device-buffer high-watermarks, and the
  XProf capture API (the old ``antidote_tpu.tracing`` shim was retired
  to a one-release import error, ISSUE 7).
- :mod:`antidote_tpu.obs.host` — the host process's account: the
  collector's pauses by generation, the partition locks' holds by
  acquiring site, and CPU by process and by Python thread kind; read
  by the registry when scraped and over every profiler capture.
- :mod:`antidote_tpu.obs.pipeline` — the pipeline snapshot (ISSUE 7):
  every registered DC's ship buffers, SubBuf gap state, gate
  backlogs, ingest staging, and stable watermarks as ONE JSON
  document, served at ``/debug/pipeline``.

Everything here is process-global, mirroring ``stats.registry`` (the
reference's metrics are BEAM-node-global the same way): all DCs in a
process share one tracer and one recorder, and the exporter surfaces
(``/debug/spans``, flight-recorder dumps) read the shared state.
"""

from __future__ import annotations

from antidote_tpu.obs.events import FlightRecorder, recorder  # noqa: F401
from antidote_tpu.obs.prof import DeviceProfiler, profiler  # noqa: F401
from antidote_tpu.obs.spans import Span, Tracer, tracer  # noqa: F401


def configure(sample_rate: float | None = None,
              capacity: int | None = None,
              dump_dir: str | None = None,
              selfcheck_set_aw: float | None = None,
              kernel_profile: bool | None = None) -> None:
    """Apply config knobs to the process-global tracer/recorder/probe/
    profiler (Node.__init__ forwards Config.trace_sample_rate & friends
    here).  ``None`` leaves a setting untouched, so tests and operators
    can override a single knob without reciting the rest.  The first
    call also starts timing the collector (``obs.host.install``)."""
    from antidote_tpu.obs import host as _host
    from antidote_tpu.obs import probe as _probe

    _host.install()

    if sample_rate is not None:
        tracer.sample_rate = float(sample_rate)
    if capacity is not None:
        tracer.set_capacity(int(capacity))
    if dump_dir is not None:
        recorder.dump_dir = dump_dir
    if selfcheck_set_aw is not None:
        _probe.SELF_CHECK_RATE = float(selfcheck_set_aw)
    if kernel_profile is not None:
        profiler.configure(enabled=bool(kernel_profile))
