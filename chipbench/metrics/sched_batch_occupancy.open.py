"""Real rows over bucket rows, mean over the window's batches, %: the
scheduler's own histogram."""


def read(run):
    c = run.get("counters", {})
    n = c.get("dl4j_serving_batch_occupancy_count")
    if not n:
        return None
    return c["dl4j_serving_batch_occupancy_sum"] / n * 100.0
