"""The whole train step's share of the chips' peak, %: forward + backward
operations per image x the images of the steps that ran in the traced
window, over window x chips x bf16 peak."""
from chipbench import work


def read(run):
    tr = run.get("trace")
    if not tr or not tr["module_s"] or not tr["window_s"]:
        return None
    step = max(tr["module_s"], key=tr["module_s"].get)
    images = tr["module_n"][step] * run["batch"]
    return (work.train_flops_per_example(run["cfg"]) * images
            / (tr["window_s"] * run["chips"] * run["peaks"]["flops_bf16"])
            * 100.0)
