"""Continuous-batching serving subsystem.

Layers (bottom-up): ``request`` (Request/Result wire format, QoS classes)
-> ``queue`` (bounded admission + rate limiting; per-class sub-queues in
QoS mode) -> ``overload`` (shed controller + deadline-feasibility
admission) -> ``slots`` (KV slot pool allocator, the live rows' prefixes)
-> ``scheduler`` (the prefill/decode step loop) -> ``router``/``fleet``
(health-aware routing over N replica schedulers, per-replica fault domains
with fence/migrate/rejoin) -> ``autoscaler`` (SLO-coupled elastic
membership over the fleet) -> ``backend`` (the ``DecodeBackend`` adapter
the pipeline phases consume). ``replay`` sits beside the stack: a seeded
synthetic-trace generator + replay driver that exercises all of it with
production-shaped load. See docs/SERVING.md.
"""

from fairness_llm_tpu.serving.autoscaler import Autoscaler
from fairness_llm_tpu.serving.backend import ServingBackend
from fairness_llm_tpu.serving.fleet import Replica, ReplicaSet
from fairness_llm_tpu.serving.replay import (
    ReplayClock,
    ReplayDriver,
    ReplayReport,
    TraceConfig,
    TraceEvent,
    generate_trace,
    read_trace,
    write_trace,
)
from fairness_llm_tpu.serving.overload import (
    DeadlineEstimator,
    ShedController,
)
from fairness_llm_tpu.serving.queue import AdmissionQueue, ClassedAdmissionQueue
from fairness_llm_tpu.serving.rollout import (
    RolloutController,
    render_rollout_report,
)
from fairness_llm_tpu.serving.request import QOS_CLASSES, Request, Result
from fairness_llm_tpu.serving.router import HealthRouter
from fairness_llm_tpu.serving.scheduler import ContinuousScheduler
from fairness_llm_tpu.serving.slots import SlotPool, SlotState

__all__ = [
    "AdmissionQueue",
    "Autoscaler",
    "ReplayClock",
    "ReplayDriver",
    "ReplayReport",
    "TraceConfig",
    "TraceEvent",
    "generate_trace",
    "read_trace",
    "write_trace",
    "ClassedAdmissionQueue",
    "ContinuousScheduler",
    "DeadlineEstimator",
    "QOS_CLASSES",
    "ShedController",
    "HealthRouter",
    "Replica",
    "ReplicaSet",
    "RolloutController",
    "render_rollout_report",
    "Request",
    "Result",
    "ServingBackend",
    "SlotPool",
    "SlotState",
]
