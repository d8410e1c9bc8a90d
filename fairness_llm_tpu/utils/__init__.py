"""Cross-cutting utilities: profiling hooks, failure containment, progress."""

from fairness_llm_tpu.utils.profiling import maybe_trace, phase_timer
from fairness_llm_tpu.utils.failures import (
    DecodeFault,
    HangFault,
    ScriptedFaultInjector,
    SweepFailed,
    require_some_decoded,
    with_failure_containment,
)
from fairness_llm_tpu.utils.progress import print_progress
from fairness_llm_tpu.utils.ratelimit import RateLimiter

__all__ = [
    "maybe_trace",
    "phase_timer",
    "DecodeFault",
    "HangFault",
    "ScriptedFaultInjector",
    "SweepFailed",
    "require_some_decoded",
    "with_failure_containment",
    "print_progress",
    "RateLimiter",
]
