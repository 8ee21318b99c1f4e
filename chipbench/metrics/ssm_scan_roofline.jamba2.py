"""The state-space prefill kernel against HBM, %: the least time one
``ssm_scan`` call could take to move its bytes (the reference's
``ssm_scan_bytes``: x, delta and z in and y out, B and C in, once a LIVE
position; the states in and out once a row; over the peak bytes/s) over the
kernel's device time a call: the trace's ops whose name holds the kernel's,
summed, over the launches of ``jit__prefill_paged`` x the Mamba layers.
Live positions and rows a launch are the program's own counters
(``serving.ssm_prefill_positions_live_total`` over the layers and
``serving.prefill_launches_total``; completed requests over launches).
``chipbench/peaks.py`` has no peak of the vector unit, so this reads the
kernel against HBM ONLY: the scan is one exponential and about seven vector
operations a state a position, and a LOW reading means that the vector unit
or the exponentials bound it, not that bytes are wasted. Without a trace,
the counters or such an op there is nothing to read."""
from chipbench.manifest import module_from

KERNEL, PROGRAM = "ssm_scan", "jit__prefill_paged"


def read(run):
    tr, c, cfg = run.get("trace"), run.get("counters", {}), run["cfg"]
    ref = module_from("reference", cfg["reference"])
    positions = c.get("dl4j_serving_ssm_prefill_positions_live_total")
    launches = c.get("dl4j_serving_prefill_launches_total")
    done = c.get("dl4j_serving_completed_total")
    if (not tr or not tr["module_n"].get(PROGRAM) or not positions
            or not launches or not done
            or not hasattr(ref, "ssm_scan_bytes")):
        return None
    kernel_s = sum(s for name, s in tr["op_s"].items() if KERNEL in name)
    if not kernel_s:
        return None
    layers = ref.mamba_layers(cfg)
    call_s = kernel_s / (tr["module_n"][PROGRAM] * layers)
    least = ref.ssm_scan_bytes(cfg, done / launches,
                               positions / layers / launches) \
        / run["peaks"]["hbm_bytes_per_s"]
    return least / call_s * 100.0
