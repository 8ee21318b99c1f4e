"""The serving path's share of the chip's peak, %: the operations of the
tokens of every request completed in the window (work.py) over window x
bf16 peak."""


def read(run):
    flops = run.get("flops_in_window")
    if not flops:
        return None
    return flops / (run["seconds"] * run["peaks"]["flops_bf16"]) * 100.0
