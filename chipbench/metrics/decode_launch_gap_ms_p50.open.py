"""Median idle time on the device just before a decode step starts, ms:
the host's round trip per token as the device sees it."""
import statistics

PROGRAM = "jit__decode_paged"


def read(run):
    tr = run.get("trace")
    gaps = (tr or {}).get("module_lead_gap_s", {}).get(PROGRAM)
    return statistics.median(gaps) * 1e3 if gaps else None
