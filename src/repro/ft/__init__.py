from .failures import (  # noqa: F401
    FaultPlan,
    InjectedFailure,
    KillPoint,
)
