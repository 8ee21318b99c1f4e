"""The closed-loop cell's turnaround between batches, ms: the open cells'
reader (sched_turnaround_ms.open)."""
from chipbench.manifest import module_from


def read(run):
    return module_from("metrics", "sched_turnaround_ms.open").read(run)
