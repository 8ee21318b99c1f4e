"""Traffic kind ``open_loop``: requests over HTTP when they are due, at the
mix's fixed rate (chipbench/serve.py)."""

from chipbench import serve

family = "serving"


def run(ctx, planted=None):
    return serve.run(ctx, "open_loop", planted)
