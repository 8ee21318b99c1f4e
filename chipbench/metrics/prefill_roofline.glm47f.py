"""One launched prefill against the chip's bf16 peak, %: the least time its
operations could take (the reference's ``prefill_flops`` of the rows x
declared positions one launch holds, by the program's counters
``serving.prefill_positions_total`` over ``serving.prefill_launches_total``
and the mix's one prompt bucket; over the peak FLOP/s) over the device time
one ``jit__prefill_paged`` launch took in the trace. Compute bounds it:
131,072 picks a layer through the grouped products, the expanded rotated
attention, the head. Without a trace, the counters (a program older than
they are) or a reference that counts a prefill there is nothing to read."""
from chipbench.manifest import module_from

PROGRAM = "jit__prefill_paged"


def read(run):
    tr, c, cfg = run.get("trace"), run.get("counters", {}), run["cfg"]
    ref = module_from("reference", cfg["reference"])
    positions = c.get("dl4j_serving_prefill_positions_total")
    launches = c.get("dl4j_serving_prefill_launches_total")
    seqs = dict(kv.split("=") for kv in run["mix"]["buckets"].split(";")
                ).get("seq", "").split(",")
    if (not tr or not tr["module_n"].get(PROGRAM) or not positions
            or not launches or not hasattr(ref, "prefill_flops")
            or len(seqs) != 1 or not seqs[0].isdigit()):
        return None
    seq = int(seqs[0])
    launch_s = tr["module_s"][PROGRAM] / tr["module_n"][PROGRAM]
    least = ref.prefill_flops(cfg, positions / launches / seq, seq) \
        / run["peaks"]["flops_bf16"]
    return least / launch_s * 100.0
