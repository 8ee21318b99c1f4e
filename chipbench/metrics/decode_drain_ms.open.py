"""What a batch's host does between its first fetch of its last step's
output and the return of ``generate``, mean over the window's batches, ms:
the remaining token fetches, the counters, trimming and releasing the pool
(the decode engine's own ``drain`` phase counter). A program without it
(before PR 39) gives nothing."""


def read(run):
    c = run.get("counters", {})
    drain = c.get("dl4j_serving_generate_drain_seconds_total")
    n = c.get("dl4j_serving_batches_total")
    if not drain or not n:
        return None
    return drain / n * 1e3
