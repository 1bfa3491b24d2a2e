"""Streaming traffic subsystem: workload generators, open-loop arrival
processes, a quiescence-free engine driver with continuous-batching
admission, hardware-style perf counters, and the in-scan observability
plane (see docs/traffic.md, docs/serving.md and docs/observability.md).

    from repro.traffic import (EngineConfig, StreamConfig, WorkloadSpec,
                               ArrivalSpec, AdmissionConfig, run_stream,
                               summarize, sojourn_summary)

    eng = EngineConfig(remotes=8, lines=64).build()
    run = run_stream(eng, StreamConfig(
        workload=WorkloadSpec("zipfian", ops=256),
        arrivals=ArrivalSpec("poisson", rate=0.05),     # open loop
        admission=AdmissionConfig(max_inflight=32, reserve=4),
        width=2))
    print(summarize(run.counters, run.msg_count))
    print(sojourn_summary(run))     # knee-curve serving metrics
"""
from .arrivals import ARRIVALS, ArrivalSchedule, check_schedule
from .config import (AdmissionConfig, ArrivalSpec, EngineConfig,
                     FleetConfig, StreamConfig, WorkloadSpec,
                     config_from_json, config_to_json)
from .counters import (Counters, LAT_EDGES, RetirementTrace, SOJOURN_EDGES,
                       acc_total, assert_counts_match, hist_percentiles,
                       replay_reference, sojourn_summary, summarize,
                       validate_run)
from .driver import StreamRun, default_steps, run_stream, stream_program
from .fleet import fleet_steps, run_fleet
from .observe import (ObserveConfig, ObsResult, OnlineViolation,
                      perfetto_events, write_perfetto)
from .workloads import WORKLOADS, Workload

__all__ = [
    "ARRIVALS", "AdmissionConfig", "ArrivalSchedule", "ArrivalSpec",
    "Counters", "EngineConfig", "FleetConfig", "LAT_EDGES",
    "ObserveConfig", "ObsResult", "OnlineViolation", "RetirementTrace",
    "SOJOURN_EDGES", "StreamConfig", "StreamRun", "WORKLOADS", "Workload",
    "WorkloadSpec", "acc_total", "assert_counts_match", "check_schedule",
    "config_from_json", "config_to_json", "default_steps", "fleet_steps",
    "hist_percentiles", "perfetto_events", "replay_reference",
    "run_fleet", "run_stream", "sojourn_summary", "stream_program",
    "summarize",
    "validate_run", "write_perfetto",
]
