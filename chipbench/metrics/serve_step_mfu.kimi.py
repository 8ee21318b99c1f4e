"""The Kimi Linear share's part of the chip's peak, %: the model's own
operations (its reference's ``request_flops``: KDA state updates, latent
attention, the expected picks of the experts held here) of every request
completed in the window, over window x bf16 peak. The share of the whole
step: prefill, decode and the host's gaps are all in the window."""
from chipbench.manifest import module_from


def read(run):
    ref = module_from("reference", run["cfg"]["reference"])
    done = [r for r in run.get("requests", [])
            if r["status"] == 200 and r["done"] <= run["seconds"]]
    if not done or not hasattr(ref, "request_flops"):
        return None
    flops = sum(ref.request_flops(run["cfg"], len(r["prompt"]),
                                  len(r["tokens"])) for r in done)
    return flops / (run["seconds"] * run["peaks"]["flops_bf16"]) * 100.0
