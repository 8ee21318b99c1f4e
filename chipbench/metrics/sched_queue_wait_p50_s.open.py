"""Median time a request waited in the scheduler's queue before its batch
opened, s: the server's own per-request records (its last 256)."""
import statistics


def read(run):
    waits = [r["queue_ms"] for r in run.get("flight", [])
             if r.get("status") == "ok" and r.get("queue_ms") is not None]
    return statistics.median(waits) / 1e3 if waits else None
