"""The Kimi Linear decode step against its HBM roofline, %: the least time
the step could take (its reference's ``decode_step_bytes``: every matrix
outside the routed experts once, the routed experts its picks touched, by
the program's own counter, the KDA states read and written, the latent rows
of the tokens its streams hold; over the peak bytes/s) over the device time
``jit__decode_paged`` took. Memory bounds it. Streams a step and tokens a
stream are the window's means (completed requests over batches; prompt +
half the new tokens); experts touched a step is the counter of held experts
with a pick, summed over the layers, over the steps counted beside it."""
from chipbench.manifest import module_from

PROGRAM = "jit__decode_paged"


def read(run):
    tr, c, cfg = run.get("trace"), run.get("counters", {}), run["cfg"]
    ref = module_from("reference", cfg["reference"])
    done = [r for r in run.get("requests", []) if r["status"] == 200]
    batches = c.get("dl4j_serving_batches_total")
    layer_steps = c.get("dl4j_serving_moe_decode_layer_steps_total")
    if (not tr or not tr["module_n"].get(PROGRAM) or not done or not batches
            or not layer_steps or not hasattr(ref, "decode_step_bytes")):
        return None
    step_s = tr["module_s"][PROGRAM] / tr["module_n"][PROGRAM]
    streams = c.get("dl4j_serving_completed_total", len(done)) / batches
    tokens = sum(len(r["prompt"]) + len(r["tokens"]) / 2
                 for r in done) / len(done)
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    touched = c["dl4j_serving_moe_decode_experts_touched_total"] \
        / (layer_steps / moe_layers)
    least = ref.decode_step_bytes(cfg, streams, streams * tokens, touched) \
        / run["peaks"]["hbm_bytes_per_s"]
    return least / step_s * 100.0
