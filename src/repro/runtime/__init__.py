from repro.runtime.compile_cache import enable_compile_cache  # noqa: F401
from repro.runtime.fault_tolerance import (  # noqa: F401
    InjectedFailure, RestartBudgetExceeded, Supervisor, SupervisorConfig,
    plan_mesh,
)
