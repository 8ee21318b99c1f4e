"""Diffusion U-Net zoo workload (ROADMAP item 5 chip, ISSUE 10 satellite).

One conv-heavy encoder/decoder DAG with skip connections, exercised two
ways: the per-layer conv cost model must attribute a resolution-split DAG,
and the compressed-DP path must train it end-to-end (the slow leg)."""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.data import MultiDataSet
from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMesh
from deeplearning4j_tpu.zoo import DiffusionUNet


def _batch(rng, n=8, size=16, c=3):
    img = rng.standard_normal((n, size, size, c)).astype(np.float32)
    t = rng.uniform(0, 1, size=(n, 1)).astype(np.float32)
    noise = rng.standard_normal((n, size, size, c)).astype(np.float32)
    return MultiDataSet(features=[img, t], labels=[noise])


def test_unet_builds_and_fits_one_batch(rng):
    net = DiffusionUNet(input_shape=(16, 16, 3), base_channels=8,
                        depth=2).init()
    ds = _batch(rng)
    net.fit([ds], epochs=2)
    assert np.isfinite(float(net.score_value))
    # skip concats really feed the decoder: dec0_a consumes 8 (up) + 8
    # (skip) channels
    dec0 = next(n for n in net.conf.nodes if n.name == "dec0_a_conv")
    assert "dec0_cat" in dec0.inputs


def test_unet_conv_cost_model_attributes_the_dag(rng):
    net = DiffusionUNet(input_shape=(16, 16, 3), base_channels=8,
                        depth=2).init()
    rep = net.cost_report(batch_size=4, publish=False)
    tags = {r.layer for r in rep.rows}
    # encoder, bottleneck conditioning, and decoder rows all present
    assert any(t.startswith("enc0_down") for t in tags), tags
    assert any(t.startswith("mid_") for t in tags), tags
    assert any(t.startswith("dec0") for t in tags), tags
    assert any(t.startswith("t_embed") for t in tags), tags
    if rep.source == "xla":
        assert rep.totals.get("flops", 0) > 0
        # conv stacks dominate a U-Net: the conv rows must carry most of
        # the attributed FLOPs (the conv cost model's valid-tap walk)
        conv_flops = sum(r.flops_fwd + r.flops_bwd for r in rep.rows
                         if "_conv" in r.layer or r.layer == "noise")
        total_attr = sum(r.flops_fwd + r.flops_bwd for r in rep.rows)
        assert conv_flops > 0.5 * total_attr, (conv_flops, total_attr)


# slow: the U-Net compiled for eight virtual devices. In tier-1
# test_unet_builds_and_fits_one_batch above fits the same DAG on one device
# and tests/test_compression.py::TestConvergenceParity::
# test_compressed_fit_tracks_exact_fit trains through the same encoded path
@pytest.mark.slow
@pytest.mark.multichip
def test_unet_compressed_dp_fit_end_to_end(rng):
    """The diffusion U-Net trains through the
    encoded-gradient DP path (threshold scheme, adaptive sparsity) on the
    8-virtual-device mesh — loss decreases, the wire accounting reports,
    and the residual state matches the DAG's gradient structure."""
    net = DiffusionUNet(input_shape=(16, 16, 3), base_channels=8,
                        depth=2).init()
    pw = ParallelWrapper(net, mesh=TrainingMesh(data=8), skew_every=0,
                         grad_compression="threshold",
                         compression_target_sparsity=1e-2)
    batches = [_batch(rng) for _ in range(4)]
    first = None
    for _ in range(4):
        for ds in batches:
            pw.step_batch(ds)
            if first is None:
                first = float(net.score_value)
    last = float(net.score_value)
    assert np.isfinite(last)
    assert last < first, (first, last)
    stats = pw.compression_stats()
    assert stats["wire_bytes"] > 0 and stats["dense_bytes"] > 0
    assert stats["threshold"] > 0
    # residual mirrors the graph's per-node gradient trees (dict-keyed)
    res = pw._comp_state["residual"]
    assert set(res.keys()) == set(net.params.keys())
    leading = {np.shape(l)[0]
               for l in jax.tree_util.tree_leaves(res)}
    assert leading == {8}  # worker-stacked
