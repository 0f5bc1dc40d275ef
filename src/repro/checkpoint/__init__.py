from .durable import DurableIndex, MutationResult, live_ids, mutation_workload
from .spatial import (
    FORMAT_VERSION,
    SnapshotError,
    load_index,
    save_index,
    snapshot_meta,
)

__all__ = [
    "DurableIndex",
    "MutationResult",
    "live_ids",
    "mutation_workload",
    "FORMAT_VERSION",
    "SnapshotError",
    "load_index",
    "save_index",
    "snapshot_meta",
]
