"""The state-space decode kernel against its HBM roofline, %: the least
time one ``ssm_step`` call could take (the reference's ``ssm_step_bytes``:
each LIVE row's state read and written once, its x, delta, z, B, C in and
its y out; over the peak bytes/s) over the kernel's device time a call: the
trace's ops whose name holds the kernel's, summed, over the launches of
``jit__decode_paged`` x the Mamba layers (one call a layer a launch). Live
rows a step are the program's own counter, ``serving.ssm_decode_states_
live_total`` over the Mamba layers and the steps (``_declared_total`` over
the layers and the bucket's rows). Memory bounds the kernel. Without a
trace, the counters or such an op there is nothing to read."""
from chipbench.manifest import module_from

KERNEL, PROGRAM = "ssm_step", "jit__decode_paged"


def read(run):
    tr, c, cfg = run.get("trace"), run.get("counters", {}), run["cfg"]
    ref = module_from("reference", cfg["reference"])
    live = c.get("dl4j_serving_ssm_decode_states_live_total")
    declared = c.get("dl4j_serving_ssm_decode_states_declared_total")
    bucket = dict(kv.split("=") for kv in run["mix"]["buckets"].split(";")
                  ).get("batch", "").split(",")
    if (not tr or not tr["module_n"].get(PROGRAM) or not live or not declared
            or not hasattr(ref, "ssm_step_bytes") or len(bucket) != 1
            or not bucket[0].isdigit()):
        return None
    kernel_s = sum(s for name, s in tr["op_s"].items() if KERNEL in name)
    if not kernel_s:
        return None
    layers = ref.mamba_layers(cfg)
    call_s = kernel_s / (tr["module_n"][PROGRAM] * layers)
    rows = live / declared * int(bucket[0])
    least = ref.ssm_step_bytes(cfg, rows) / run["peaks"]["hbm_bytes_per_s"]
    return least / call_s * 100.0
