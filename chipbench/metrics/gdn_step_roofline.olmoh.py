"""The Gated DeltaNet decode kernel against its HBM roofline, %: the least
time one ``kda_decode`` call could take (the reference's
``gdn_step_bytes``: each LIVE row's (heads, 96, 192) float32 state read
and written once, its q, k, decay, beta and v in and its o out; over the
peak bytes/s) over the kernel's device time a call: the trace's ops whose
name holds the kernel's, summed, over the launches of
``jit__decode_paged`` x the linear layers (one call a layer a launch).
Live rows a step are the program's own counter,
``serving.gdn_decode_states_live_total`` over the linear layers and the
steps (``_declared_total`` over the layers and the bucket's rows). Memory
bounds the kernel. Without a trace, the counters or such an op there is
nothing to read."""
from chipbench.manifest import module_from

KERNEL, PROGRAM = "kda_decode", "jit__decode_paged"


def read(run):
    tr, c, cfg = run.get("trace"), run.get("counters", {}), run["cfg"]
    ref = module_from("reference", cfg["reference"])
    live = c.get("dl4j_serving_gdn_decode_states_live_total")
    declared = c.get("dl4j_serving_gdn_decode_states_declared_total")
    bucket = dict(kv.split("=") for kv in run["mix"]["buckets"].split(";")
                  ).get("batch", "").split(",")
    if (not tr or not tr["module_n"].get(PROGRAM) or not live or not declared
            or not hasattr(ref, "gdn_step_bytes") or len(bucket) != 1
            or not bucket[0].isdigit()):
        return None
    kernel_s = sum(s for name, s in tr["op_s"].items() if KERNEL in name)
    if not kernel_s:
        return None
    call_s = kernel_s / (tr["module_n"][PROGRAM] * ref.gdn_layers(cfg))
    rows = live / declared * int(bucket[0])
    least = ref.gdn_step_bytes(cfg, rows) / run["peaks"]["hbm_bytes_per_s"]
    return least / call_s * 100.0
