"""Traffic kind ``closed_loop``: ``clients`` callers over HTTP, each sending
its next request when the last returns (chipbench/serve.py)."""

from chipbench import serve

family = "serving"


def run(ctx, planted=None):
    return serve.run(ctx, "closed_loop", planted)
