"""The Jamba decode step against its HBM roofline, %: the least time the
step could take (its reference's ``decode_step_bytes``: every matrix once,
the embedding once as the head, the live streams' states and convolution
tails read and written in the 26 Mamba layers, the key/value rows the
streams hold in the two attention layers; over the peak bytes/s) over the
device time one ``jit__decode_paged`` launch took. Memory bounds it. Streams
a step and tokens a stream are the window's means, as
``decode_step_roofline.kimi`` takes them (completed requests over batches;
prompt + half the new tokens). Without a trace there is nothing to read."""
from chipbench.manifest import module_from

PROGRAM = "jit__decode_paged"


def read(run):
    tr, c, cfg = run.get("trace"), run.get("counters", {}), run["cfg"]
    ref = module_from("reference", cfg["reference"])
    done = [r for r in run.get("requests", []) if r["status"] == 200]
    batches = c.get("dl4j_serving_batches_total")
    if (not tr or not tr["module_n"].get(PROGRAM) or not done or not batches
            or not hasattr(ref, "decode_step_bytes")):
        return None
    step_s = tr["module_s"][PROGRAM] / tr["module_n"][PROGRAM]
    streams = c.get("dl4j_serving_completed_total", len(done)) / batches
    tokens = sum(len(r["prompt"]) + len(r["tokens"]) / 2
                 for r in done) / len(done)
    least = ref.decode_step_bytes(cfg, streams, streams * tokens) \
        / run["peaks"]["hbm_bytes_per_s"]
    return least / step_s * 100.0
