"""Lustre-like parallel-file-system model: the substrate CARAT tunes.

The paper deploys on real Lustre 2.15 (CloudLab); this container has no PFS,
so the I/O path is rebuilt as a deterministic *interval-fluid queueing model*
with carried state (per-client dirty-cache level, per-OST queue delay). Each
probe interval (0.5 s, matching the paper) is resolved analytically:
request arrival -> dirty-page admission -> RPC-extent formation (fill /
timeout / cache-pressure dispatch) -> bounded in-flight transport -> shared
per-OST service queues with per-RPC fixed cost. All of the paper's §II
bottleneck mechanisms (under-filled extents, cache fragmentation, server-side
congestion, cache-limit throttling, flush bursts, in-place-update absorption)
are first-class terms of the model, so the tuning trade-offs CARAT learns are
the paper's trade-offs, not artifacts.
"""
from repro.storage.params import PFSParams, PAGE_SIZE
from repro.storage.workloads import (WorkloadSpec, WORKLOADS, get_workload,
                                     idle_workload)
from repro.storage.client import IOClient, ClientConfig
from repro.storage.pfs import ClusterFeedback, PFSCluster
from repro.storage.sim import SchedulePolicy, Simulation, SimResult
from repro.storage.soa import (DemandBatch, PlanBatch, SoAClientView,
                               SoACore)
from repro.storage.replay import (Trace, TraceRecord, WorkloadSchedule,
                                  SchedulePhase, parse_trace, render_trace,
                                  load_trace, bundled_traces,
                                  load_bundled_trace, compile_trace,
                                  segment_phases, schedule_from_names,
                                  simulation_from_schedules,
                                  simulation_from_trace, synthesize_trace)

__all__ = [
    "PFSParams", "PAGE_SIZE", "WorkloadSpec", "WORKLOADS", "get_workload",
    "idle_workload", "IOClient", "ClientConfig", "PFSCluster",
    "ClusterFeedback", "Simulation", "SimResult", "SchedulePolicy",
    "SoACore", "SoAClientView", "PlanBatch", "DemandBatch",
    "Trace", "TraceRecord", "WorkloadSchedule", "SchedulePhase",
    "parse_trace", "render_trace", "load_trace", "bundled_traces",
    "load_bundled_trace", "compile_trace", "segment_phases",
    "schedule_from_names", "simulation_from_schedules",
    "simulation_from_trace", "synthesize_trace",
]
