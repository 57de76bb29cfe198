"""Unified device-tick runtime with QoS classes — one token-budget
executor for serving (``INTERACTIVE``), engine-plane embed/rerank/LLM
micro-batches (``LLM_RERANK``) and bulk ingest (``BULK_INGEST``).

See :mod:`pathway_tpu.runtime.executor` for the policy (strict priority
with budget, starvation-bounded minimum shares, WindVE-style per-class
admission control) and README "Operations: unified runtime & QoS
classes" for the operator view.
"""

from .executor import (
    AdmissionRefused,
    DeadlineExceeded,
    DeviceTickRuntime,
    QoS,
    WorkGroup,
    WorkItem,
    budget_chunks,
    configure,
    estimate_tokens,
    get_runtime,
    reset_runtime,
    runtime_settings,
    runtime_stats_if_active,
)

__all__ = [
    "AdmissionRefused",
    "DeadlineExceeded",
    "DeviceTickRuntime",
    "QoS",
    "WorkGroup",
    "WorkItem",
    "budget_chunks",
    "configure",
    "estimate_tokens",
    "get_runtime",
    "reset_runtime",
    "runtime_settings",
    "runtime_stats_if_active",
]
