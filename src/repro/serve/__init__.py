"""Solver serving: program-once/solve-many, async SLOs, replicated fleet.

The LM generation engine that used to live here (`serve.Engine`,
`serve.serve_step`) moved to `repro.models.lm_engine` /
`repro.models.serve_step` - this package is the *solver* serving stack.
"""
from repro.serve.solver_service import SolverService, MatrixStats  # noqa: F401
from repro.serve.async_engine import (  # noqa: F401
    AsyncSolverEngine, BackpressureError, DeadlineExceededError,
    EngineError, EngineStats, EngineStoppedError, SolveResult)
from repro.serve.router import (  # noqa: F401
    FleetError, FleetStats, NoReplicaAvailableError, ReplicatedSolverFleet)
from repro.serve.maintenance import (  # noqa: F401
    BlockTrend, DeviceClock, MaintenanceConfig, MatrixMaintenance)
