"""Serving runtime: arm engine, ThriftLLM router, plan service, scheduler,
online estimation feedback, fault injection + degradation tracking."""
from repro.distributed.fault import ArmFaultSpec, FaultPolicy

from .compile_cache import configure_compile_cache
from .engine import LMArm, OracleArm, PoolEngine, USD_PER_FLOP
from .feedback import (
    DegradationTracker,
    FeedbackLog,
    FeedbackReport,
    FeedbackShard,
    merge_counts,
)
from .plans import GroupPlan, PlanService
from .replica import ReplicaSet, ReplicaWorker
from .router import PendingRoute, RouteResult, ThriftRouter
from .scheduler import (
    BatchScheduler,
    BlockFuture,
    CostLedger,
    Request,
    RequestFuture,
    RequestResult,
)

__all__ = [
    "LMArm", "OracleArm", "PoolEngine", "USD_PER_FLOP",
    "FeedbackLog", "FeedbackReport", "DegradationTracker",
    "FeedbackShard", "merge_counts",
    "GroupPlan", "PlanService",
    "ThriftRouter", "RouteResult", "PendingRoute",
    "BatchScheduler", "Request", "RequestFuture", "RequestResult",
    "BlockFuture", "CostLedger",
    "ReplicaSet", "ReplicaWorker",
    "ArmFaultSpec", "FaultPolicy",
    "configure_compile_cache",
]
