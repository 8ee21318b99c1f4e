"""The Gated DeltaNet prefill kernel against its roofline, %: the least
time one ``kda_prefill`` call could take, the larger of its operations
over the bf16 matrix peak and its bytes over HBM's (the reference's
``gdn_chunk_work`` of one call's LIVE chunks: every head's chunk products;
q, k, v, decay and beta in and o out a position of a live chunk; the
final state out a row), over the kernel's device time a call: the trace's
ops whose name holds the kernel's, summed, over the launches of
``jit__prefill_paged`` x the linear layers. Live chunks a launch are the
program's own counter (``serving.gdn_prefill_chunks_live_total`` over
``serving.prefill_launches_total``: a layer's walk, counted once a
prefill), rows a launch the completed requests over the launches.
Without a trace, the counters or such an op there is nothing to read."""
from chipbench.manifest import module_from

KERNEL, PROGRAM = "kda_prefill", "jit__prefill_paged"


def read(run):
    tr, c, cfg = run.get("trace"), run.get("counters", {}), run["cfg"]
    ref = module_from("reference", cfg["reference"])
    chunks = c.get("dl4j_serving_gdn_prefill_chunks_live_total")
    launches = c.get("dl4j_serving_prefill_launches_total")
    done = c.get("dl4j_serving_completed_total")
    if (not tr or not tr["module_n"].get(PROGRAM) or not chunks
            or not launches or not done
            or not hasattr(ref, "gdn_chunk_work")):
        return None
    kernel_s = sum(s for name, s in tr["op_s"].items() if KERNEL in name)
    if not kernel_s:
        return None
    call_s = kernel_s / (tr["module_n"][PROGRAM] * ref.gdn_layers(cfg))
    flops, moved = ref.gdn_chunk_work(cfg, done / launches, chunks / launches)
    peaks = run["peaks"]
    least = max(flops / peaks["flops_bf16"], moved / peaks["hbm_bytes_per_s"])
    return least / call_s * 100.0
