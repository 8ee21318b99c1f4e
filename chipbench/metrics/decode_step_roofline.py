"""The paged decode step against its HBM roofline, %: the least time the
step could take (every multiplied weight once, plus the K and V of the
tokens its streams hold, over the peak bytes/s) over the device time it
took. Memory bounds it: its operations over the peak FLOP/s are far less.
Streams per step and tokens per stream are the window's means (completed
requests over batches; prompt + half the new tokens)."""
from chipbench import work

PROGRAM = "jit__decode_paged"


def read(run):
    tr, c = run.get("trace"), run.get("counters", {})
    done = [r for r in run.get("requests", []) if r["status"] == 200]
    batches = c.get("dl4j_serving_batches_total")
    if not tr or not tr["module_n"].get(PROGRAM) or not done or not batches:
        return None
    step_s = tr["module_s"][PROGRAM] / tr["module_n"][PROGRAM]
    streams = c.get("dl4j_serving_completed_total", len(done)) / batches
    tokens = sum(len(r["prompt"]) + len(r["tokens"]) / 2
                 for r in done) / len(done)
    least = work.decode_step_bytes(run["cfg"], int(streams * tokens)) \
        / run["peaks"]["hbm_bytes_per_s"]
    return least / step_s * 100.0
