"""deepspeed_tpu.serving.observatory — the serving stack's time
dimension (ISSUE 13): open-loop load generation (seeded arrival
processes + heavy-tailed lengths, submitted on schedule regardless of
completions — the DistServe/FastGen evaluation shape closed loops
cannot produce), bounded per-tick metric time series on the existing
step seams, and a recompile flight recorder that turns mid-serve XLA
compiles into counted, timestamped, trace-visible events.

The workload generator and the open-loop driver have no caller in the
package or the benchmark yet (tests/test_observatory.py and
examples/serve_requests.py drive them): the benchmark's traffic lives
under `benchmark/traffic_kinds/`.
"""
from .workload import ARRIVAL_PROCESSES, WorkloadGenerator, WorkloadItem
from .driver import (OpenLoopDriver, OpenLoopResult, VirtualClock,
                     calibrate_service_rate)
from .metrics import FleetMetricsSampler, MetricRing, MetricsSampler
from .recompile import (COMPILE_EVENTS, RecompileFlightRecorder,
                        program_cache_census)

__all__ = [
    "ARRIVAL_PROCESSES", "WorkloadGenerator", "WorkloadItem",
    "OpenLoopDriver", "OpenLoopResult", "VirtualClock",
    "calibrate_service_rate",
    "MetricRing", "MetricsSampler", "FleetMetricsSampler",
    "COMPILE_EVENTS", "RecompileFlightRecorder", "program_cache_census",
]
