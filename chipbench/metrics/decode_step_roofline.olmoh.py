"""The Olmo Hybrid decode step against its HBM roofline, %: the least time
the step could take (its reference's ``decode_step_bytes``: every matrix
once, the head's among them, the live streams' delta-rule states and
convolution tails read and written in the 12 linear layers, the key/value
rows the streams hold in the 4 full layers; over the peak bytes/s) over
the device time one ``jit__decode_paged`` launch took. The reading is
``decode_step_roofline.jamba2``'s, whose reader asks whichever reference
the configuration names; it is read through that reader, not copied."""
from chipbench.manifest import module_from


def read(run):
    return module_from("metrics", "decode_step_roofline.jamba2").read(run)
