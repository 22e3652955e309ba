"""Memory-governed serving: WSMC capacity prediction drives continuous
batching over a slotted KV pool.

`trace`, `engine` and `faults` touch no device (the scheduler is a
deterministic state machine and the chaos wrappers inject into it; the
engine uses jax only for its profiler span); the
jax-backed executor lives in `repro.serving.executor` and is imported
lazily so planning/metrics code never touches device state.
"""
from repro.serving.engine import (  # noqa: F401
    AUDIT_MODES, AllocationFault, BlockAllocator, Cancellation, Completion,
    DoubleFree, Engine, EngineFault, EngineSnapshot, LadderConfig,
    LedgerCorruption, NegativeRefcount, POLICIES, PoolExhausted,
    RESERVATIONS, RUNG_NAMES, ScriptedExecutor, ServeReport,
    TransientExecutorError,
)
from repro.serving.faults import (  # noqa: F401
    ChaosAllocator, ChaosExecutor, FaultPlan, leak_check,
    survivor_mismatches,
)
from repro.serving.trace import (  # noqa: F401
    LengthStats, OnlineLengthStats, Request, describe_trace, length_stats,
    synthetic_trace, trace_context,
)


def __getattr__(name):
    if name in ("JaxExecutor", "PagedJaxExecutor"):
        from repro.serving import executor
        return getattr(executor, name)
    if name in ("AgreementReport", "token_agreement", "measure_bend"):
        from repro.serving import quality
        return getattr(quality, name)
    raise AttributeError(name)
