"""Continuous-batching serving subsystem: request lifecycle, admission
control, page-pool pressure handling, and the replicated front door.
See engine.py for the single-replica architecture, router.py for the
fleet coordinator, and docs/DESIGN.md for the failure models."""

from ..utils import profiling as _profiling  # noqa: F401  (TELEMETRY spans -> profiler)
from .control import ControlConfig, Controller, Decision
from .engine import Engine, EngineConfig, check_accounting
from .journal import (
    JournalCorrupt,
    RequestJournal,
    replay_unfinished,
    request_from_record,
    request_to_record,
)
from .postdecode import PostDecodePipeline, StageConfig, StageSpec
from .router import ReplicaState, Router, RouterConfig
from .scheduler import PagePool, Scheduler, TokenBudget, pages_for
from .types import (
    Clock,
    EngineUnsupportedModel,
    FakeClock,
    Outcome,
    RejectReason,
    Request,
    RequestResult,
)

__all__ = [
    "Clock",
    "ControlConfig",
    "Controller",
    "Decision",
    "Engine",
    "EngineConfig",
    "EngineUnsupportedModel",
    "FakeClock",
    "JournalCorrupt",
    "Outcome",
    "PagePool",
    "PostDecodePipeline",
    "RejectReason",
    "ReplicaState",
    "Request",
    "RequestJournal",
    "RequestResult",
    "Router",
    "RouterConfig",
    "Scheduler",
    "StageConfig",
    "StageSpec",
    "TokenBudget",
    "check_accounting",
    "pages_for",
    "replay_unfinished",
    "request_from_record",
    "request_to_record",
]
