"""Traffic kind ``staged_ring``: batches staged on the device in set-up,
``net._fit_batch`` back to back (chipbench/train.py)."""

from chipbench import train

family = "training"


def run(ctx, planted=None):
    return train.run(ctx, planted)
