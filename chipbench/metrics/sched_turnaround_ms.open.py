"""The time the device waited on the host between one batch and the next,
mean over the window's pairs of batches, ms: the scheduler's own counters
(a batch's prefill launch less the later of the last batch's return of its
first fetch of its last step's output and this batch's head's submit). A
program without them (before PR 39) gives nothing."""


def read(run):
    c = run.get("counters", {})
    total = c.get("dl4j_serving_batch_turnaround_seconds_total")
    n = c.get("dl4j_serving_batch_turnarounds_total")
    if not total or not n:
        return None
    return total / n * 1e3
