"""Driver benchmark: prints ONE JSON line
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra_metrics": [...]}.

The headline metric is tracked metric 1 (ResNet-50 train-step
images/sec/chip vs the 8,000 img/s/chip north star). ``extra_metrics`` carries
the other two tracked metrics so every round records all three driver-side
(VERDICT r1 weak #2):
  2. BERT-base fine-tune samples/sec (batch 32, seq 128, bf16, native encoder)
  3. data-parallel scaling curve 1->8 devices. No multi-chip hardware is
     attached, so this runs in a subprocess on a virtual 8-device CPU mesh
     (XLA_FLAGS=--xla_force_host_platform_device_count=8) — it measures the
     sharding program's parallel efficiency shape, not chip ICI.

Device-sized phases (ResNet-50, BERT-base, flash 2048, the LSTM char-RNN)
run only where ``jax.devices()[0].platform == "tpu"``, and one that raises
ends the run with a non-zero exit code. On a host with no TPU they are not
run and nothing is printed under their metric names; the host-side phases
(overhead ratios, counts, virtual-device dry runs) run everywhere.

Methodology per metric: synthetic data staged on device ONCE; warmup past all
XLA recompiles; timed steady-state steps; completion forced by fetching the
final scalar loss to the host. The whole jitted train step is measured:
forward, reverse AD, updater, parameter write. bfloat16 compute with fp32
accumulation — the MXU-native policy. EVERY metric is median-of-3 with an
explicit ``noise`` field (half the min-max spread over the median — the DP
proxy's r4 definition, extended to all metrics per VERDICT r5 weak #2).
"""

from __future__ import annotations

import itertools
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

NORTH_STAR_IMG_PER_SEC = 8000.0  # BASELINE.json north_star, TPU v5e per chip


def _med3(measure, runs: int = 3):
    """median-of-N measurement + spread (VERDICT r5 weak #2: EVERY bench
    metric carries an explicit noise field, not just the DP proxy). Returns
    (median, noise_string); noise = half the min-max spread over the median,
    the same definition the DP proxy has used since r4."""
    vals = sorted(measure() for _ in range(runs))
    med = vals[runs // 2]
    noise = (vals[-1] - vals[0]) / 2.0 / med if med else 0.0
    return med, f"±{round(100 * noise, 1)}% ({runs}-sample spread/2)"


def _bench_net(net, x, y, steps: int, min_seconds: float = 2.0):
    import jax

    x = jax.device_put(x)
    y = jax.device_put(y)
    for _ in range(4):  # warm past every recompile (sharding commitment)
        net._fit_batch(x, y)
    float(net.score_value)  # force completion of the warmup chain
    t0 = time.perf_counter()
    done = 0
    while done < steps or (time.perf_counter() - t0) < min_seconds:
        net._fit_batch(x, y)
        done += 1
        if done >= steps * 10:
            break
    float(net.score_value)  # host fetch: waits for the full step chain
    dt = time.perf_counter() - t0
    return done * x.shape[0] / dt


def bench_resnet50(batch: int, image: int, steps: int):
    from deeplearning4j_tpu.zoo import ResNet50

    net = ResNet50(num_classes=1000, input_shape=(image, image, 3),
                   compute_dtype="bfloat16").init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, image, image, 3)).astype(np.float32)
    labels = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, size=batch)]
    ips, noise = _med3(lambda: _bench_net(net, x, y=labels, steps=steps))
    return {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "model": f"zoo.ResNet50 {image}px classes=1000 B={batch} bf16",
        "value": round(ips, 2),
        "noise": noise,
        "unit": "images/sec/chip",
        # vs the 8,000 img/s/chip v5e north star (BASELINE.json)
        "vs_baseline": round(ips / NORTH_STAR_IMG_PER_SEC, 4),
    }


def bench_bert(batch: int, seq: int, steps: int):
    """Tracked metric 2: BERT-base fine-tune samples/sec (BASELINE config #4,
    native encoder — one jitted train step; the TF-import route produces the
    same compiled program shape)."""
    from deeplearning4j_tpu.zoo.bert import Bert

    model = Bert.base(
        task="classification", num_classes=2, max_length=seq,
        compute_dtype="bfloat16")
    net = model.init()
    rng = np.random.default_rng(0)
    tok = rng.integers(0, model.vocab_size, size=(batch, seq))
    seg = np.zeros((batch, seq))
    x = np.stack([tok, seg], axis=-1).astype(np.int32)
    labels = np.eye(2, dtype=np.float32)[rng.integers(0, 2, size=batch)]
    sps, noise = _med3(lambda: _bench_net(net, x, y=labels, steps=steps))
    return {
        "metric": "bert_base_finetune_samples_per_sec_per_chip",
        "model": f"zoo.bert.Bert.base B={batch} seq={seq} bf16",
        "value": round(sps, 2),
        "noise": noise,
        "unit": "samples/sec/chip",
        "vs_baseline": None,  # no reference number exists
    }


_SCALING_CHILD = r"""
import json, os, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMesh
from deeplearning4j_tpu.data import ArrayDataSetIterator
from deeplearning4j_tpu.zoo import ResNet50

# Fixed GLOBAL batch: the unsharded step and the 8-way-sharded step do the
# same total work on the same host cores, so efficiency = TP8/TP1 isolates
# the cost the SPMD partitioner adds (collectives, halo, reshards). The model
# is the tracked flagship (zoo ResNet-50, shrunk to 32px so the single-core
# CPU host finishes; same graph topology / collective structure as 224px).
# On real multi-chip hardware this same harness measures true scaling.
def throughput(n_dev, global_batch=64, steps=4):
    net = ResNet50(num_classes=16, input_shape=(32, 32, 3)).init()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(global_batch, 32, 32, 3)).astype(np.float32)
    ys = np.eye(16, dtype=np.float32)[rng.integers(0, 16, global_batch)]
    it = ArrayDataSetIterator(xs, ys, batch=global_batch)
    w = ParallelWrapper(net, mesh=TrainingMesh(data=n_dev, devices=jax.devices()[:n_dev]))
    w.fit(it, epochs=1)  # warm past compile
    t0 = time.perf_counter()
    for _ in range(steps):
        w.fit(it, epochs=1)
    jax.block_until_ready(jax.tree_util.tree_leaves(net.params)[0])
    return global_batch * steps / (time.perf_counter() - t0)

# median-of-3 (VERDICT r3 weak #1): single samples on the 1-core host swing
# ±15% with scheduler noise — report the median efficiency and the spread
effs, pairs = [], []
for _ in range(3):
    t1 = throughput(1)
    t8 = throughput(8)
    effs.append(t8 / t1)
    pairs.append((t1, t8))
effs.sort()
med = effs[1]
noise = (effs[-1] - effs[0]) / 2.0 / med if med else 0.0
print(json.dumps({"pairs": pairs, "efficiencies": effs, "efficiency": med,
                  "noise_frac": round(noise, 4)}))
"""


def bench_scaling():
    """Tracked metric 3 proxy: SPMD partitioning efficiency of the flagship
    (zoo ResNet-50) DP train step on a virtual 8-device CPU mesh at fixed
    global batch (sharded vs unsharded throughput on the same host cores).
    True 8->256 chip scaling needs the hardware this environment does not
    attach; the single-core host further depresses the absolute number —
    only the same-host trend is meaningful."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SCALING_CHILD], env=env,
                         capture_output=True, text=True, timeout=1500,
                         cwd=os.path.dirname(os.path.abspath(__file__)))
    line = [l for l in out.stdout.strip().splitlines() if l.startswith("{")][-1]
    r = json.loads(line)
    return {
        "metric": "dp_sharding_efficiency_8dev_virtual_cpu",
        "model": "zoo.ResNet50 32px classes=16 global_batch=64 fp32 (flagship topology, CPU-sized)",
        "value": round(r["efficiency"], 4),  # median of 3
        "noise": f"±{round(100 * r.get('noise_frac', 0), 1)}% (3-sample spread/2, 1-core host)",
        "unit": "fraction",
        "vs_baseline": round(r["efficiency"] / 0.90, 4),  # ≥90% north star
    }


_ZERO_MEMORY_CHILD = r"""
import json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMesh, gspmd

# ~25M params with Adam -> ~202 MB of fp32 moments replicated per device;
# ZeRO shards every 8-divisible moment leaf over the 'data' axis
conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3)).list()
        .layer(DenseLayer(n_in=2048, n_out=4096, activation="relu"))
        .layer(DenseLayer(n_in=4096, n_out=4096, activation="relu"))
        .layer(OutputLayer(n_in=4096, n_out=16, loss="mcxent",
                           activation="softmax"))
        .set_input_type(InputType.feed_forward(2048)).build())
net = MultiLayerNetwork(conf).init()
replicated = gspmd.tree_bytes(net.opt_states)
pw = ParallelWrapper(net, mesh=TrainingMesh(data=8), zero_optimizer=True,
                     skew_every=0)
rng = np.random.default_rng(0)
xs = rng.standard_normal((16, 2048)).astype(np.float32)
ys = np.eye(16, dtype=np.float32)[rng.integers(0, 16, 16)]
pw.fit([DataSet(xs, ys)], epochs=1)  # build + one real step
per_dev = pw.opt_state_bytes_per_device()
print(json.dumps({"per_device": int(per_dev), "replicated": int(replicated),
                  "ratio": per_dev / replicated,
                  "sharded_fraction": gspmd.sharded_fraction(pw._zero_specs)}))
"""


def bench_zero_memory():
    """ZeRO satellite metric: optimizer-state bytes ONE device holds for
    the 25M-param Adam net on the 8-virtual-device mesh (arXiv:2004.13336
    cross-replica weight-update sharding). Replicated baseline is the same
    tree's full footprint; the ratio is the honest ~1/N claim. Runs in a
    subprocess so the 8-device CPU topology never leaks into the parent
    (which may hold the real chip)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _ZERO_MEMORY_CHILD], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.abspath(__file__)))
    line = [l for l in out.stdout.strip().splitlines()
            if l.startswith("{")][-1]
    r = json.loads(line)
    return {
        "metric": "zero_optimizer_memory_bytes_per_device",
        "model": (f"25M-param dense Adam, 8-dev ZeRO "
                  f"(replicated {r['replicated']} B, ratio "
                  f"{r['ratio']:.4f}, sharded fraction "
                  f"{r['sharded_fraction']:.2f})"),
        "value": r["per_device"],
        "unit": "bytes/device",
        "vs_baseline": round(r["ratio"], 4),  # vs replicated footprint
    }


_PIPELINE_CHILD = r"""
import json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.parallel import PipelinedTrainer, TrainingMesh, gspmd

# stage-dominated net (4 x 1024x1024 dense stage layers + Adam moments):
# replicated param+opt footprint ~50 MB; the (data=2, model=2, pipe=2)
# placement pipe-shards the stacked stage params and ZeRO-shards the
# moments over 'data' — bytes ONE device holds is the gated number
STAGES, N_MICRO = 2, 4
W = 1024
conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
        .pipe_stages(STAGES).n_micro(N_MICRO).list()
        .layer(DenseLayer(n_in=256, n_out=W, activation="relu"))
        .stage_boundary()
        .layer(DenseLayer(n_in=W, n_out=W, activation="tanh"))
        .layer(DenseLayer(n_in=W, n_out=W, activation="relu"))
        .stage_boundary()
        .layer(DenseLayer(n_in=W, n_out=W, activation="tanh"))
        .layer(DenseLayer(n_in=W, n_out=W, activation="relu"))
        .stage_boundary()
        .layer(OutputLayer(n_in=W, n_out=16, loss="mcxent",
                           activation="softmax"))
        .set_input_type(InputType.feed_forward(256)).build())
net = MultiLayerNetwork(conf).init()
replicated = gspmd.tree_bytes(net.params) + gspmd.tree_bytes(net.opt_states)
pt = PipelinedTrainer(net, mesh=TrainingMesh(data=2, model=2, pipe=2),
                      replicas=2, skew_every=0)
rng = np.random.default_rng(0)
xs = rng.standard_normal((16, 256)).astype(np.float32)
ys = np.eye(16, dtype=np.float32)[rng.integers(0, 16, 16)]
pt.fit([DataSet(xs, ys)], epochs=1)  # build + one real pipelined step
per_dev = pt.train_state_bytes_per_device()
print(json.dumps({
    "per_device": int(per_dev), "replicated": int(replicated),
    "ratio": per_dev / replicated, "stages": STAGES, "n_micro": N_MICRO,
    "bubble": pt.bubble_fraction,
    "param_per_device": int(pt.param_bytes_per_device()),
    "opt_per_device": int(pt.opt_state_bytes_per_device()),
    "loss_finite": bool(np.isfinite(float(net.score_value)))}))
"""


def bench_pipeline():
    """Pipeline-parallel fit() metrics (ISSUE 14, BENCH_r10 headline):
    ``pipeline_param_bytes_per_device`` — param+optimizer bytes ONE device
    holds for the stage-dominated net on the (data=2, model=2, pipe=2)
    8-virtual-device mesh (stacked stage params P('pipe'), moments
    ZeRO-sharded; the "model too big for one chip as a config knob"
    number) — and ``pipeline_bubble_fraction`` — the GPipe fill-drain
    schedule's idle fraction (S-1)/(n_micro+S-1) at the committed
    (stages=2, n_micro=4) config. Both are DETERMINISTIC byte/schedule
    accounting: CPU proves placement, equivalence, and the schedule's
    arithmetic, it cannot rank pipelined wall-clock (bubbles only cost
    time on real chips — the r6 convention; docs/DISTRIBUTED.md)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _PIPELINE_CHILD], env=env,
                         capture_output=True, text=True, timeout=1500,
                         cwd=os.path.dirname(os.path.abspath(__file__)))
    line = [l for l in out.stdout.strip().splitlines()
            if l.startswith("{")][-1]
    r = json.loads(line)
    assert r["loss_finite"], r
    return [
        {
            "metric": "pipeline_param_bytes_per_device",
            "model": (f"4x{1024}-wide stage-dominated Adam net on "
                      f"(data=2, model=2, pipe=2), stages={r['stages']} "
                      f"(replicated {r['replicated']} B, params/dev "
                      f"{r['param_per_device']} B + opt/dev "
                      f"{r['opt_per_device']} B, ratio {r['ratio']:.4f} "
                      f"≈ 1/pipe_stages; deterministic byte accounting — "
                      f"CPU proves placement+equivalence, cannot rank "
                      f"pipelined wall-clock)"),
            "value": r["per_device"],
            "noise": "±0.0% (deterministic byte accounting)",
            "unit": "bytes/device",
            "vs_baseline": round(r["ratio"], 4),  # vs replicated footprint
        },
        {
            "metric": "pipeline_bubble_fraction",
            "model": (f"GPipe fill-drain schedule, stages={r['stages']} "
                      f"n_micro={r['n_micro']}: (S-1)/(n_micro+S-1) — "
                      f"computed from the schedule, never timed on this "
                      f"CPU container (bubbles cost wall-clock only on "
                      f"real chips)"),
            "value": round(r["bubble"], 6),
            "noise": "±0.0% (schedule arithmetic)",
            "unit": "fraction",
            # vs the degenerate n_micro=1 schedule at S=2:
            # (S-1)/(1+S-1) = 0.5 — the no-microbatching worst case
            "vs_baseline": round(r["bubble"] / 0.5, 4),
        },
    ]


_COMPRESSION_CHILD = r"""
import json, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMesh

# the ZeRO bench's 25M-param Adam topology — the DP workload whose gradient
# exchange the encoded all-reduce compresses (ISSUE 10 acceptance: ratio
# <= 0.1 at the adaptive target sparsity)
def build(comp):
    b = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3)))
    if comp:
        b = b.grad_compression("threshold", threshold=1e-3,
                               target_sparsity=1e-3)
    conf = (b.list()
            .layer(DenseLayer(n_in=2048, n_out=4096, activation="relu"))
            .layer(DenseLayer(n_in=4096, n_out=4096, activation="relu"))
            .layer(OutputLayer(n_in=4096, n_out=16, loss="mcxent",
                               activation="softmax"))
            .set_input_type(InputType.feed_forward(2048)).build())
    return MultiLayerNetwork(conf).init()

rng = np.random.default_rng(0)
xs = rng.standard_normal((16, 2048)).astype(np.float32)
ys = np.eye(16, dtype=np.float32)[rng.integers(0, 16, 16)]
ds = [DataSet(xs, ys)]

def timed_fit(comp, steps=12):
    net = build(comp)
    pw = ParallelWrapper(net, mesh=TrainingMesh(data=8), skew_every=0,
                         grad_compression=None)
    pw.fit(ds, epochs=2)  # compile + settle the adaptive threshold
    t0 = time.perf_counter()
    pw.fit(ds, epochs=steps)
    jax.block_until_ready(jax.tree_util.tree_leaves(net.params)[0])
    dt = time.perf_counter() - t0
    stats = pw.compression_stats() if comp else None
    return dt, stats, float(net.score_value)

dt_comp, stats, loss_c = timed_fit(True)
dt_exact, _, loss_e = timed_fit(False)
print(json.dumps({
    "ratio": stats["ratio"], "wire_bytes": stats["wire_bytes"],
    "dense_bytes": stats["dense_bytes"], "threshold": stats["threshold"],
    "nnz": stats["nnz"], "elements": stats["elements"],
    "compressed_step_seconds": dt_comp / 12,
    "exact_step_seconds": dt_exact / 12,
    "loss_compressed": loss_c, "loss_exact": loss_e,
}))
"""


def bench_compression_ratio():
    """encoded_allreduce_wire_bytes_ratio: deterministic wire accounting of
    the encoded gradient all-reduce (parallel/compression.py) on the
    25M-param DP workload — one worker's sparse threshold payload vs its
    dense fp32 gradient, at the adaptive target sparsity (1e-3). The byte
    math is exact and CPU-provable; the wall-clock A/B rides along in the
    model string but CANNOT rank the paths on this container (the encode
    costs CPU FLOPs while the wire savings only pay on a real DCN — the r6
    convention; docs/DISTRIBUTED.md#gradient-compression)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _COMPRESSION_CHILD], env=env,
                         capture_output=True, text=True, timeout=1500,
                         cwd=os.path.dirname(os.path.abspath(__file__)))
    line = [l for l in out.stdout.strip().splitlines()
            if l.startswith("{")][-1]
    r = json.loads(line)
    return {
        "metric": "encoded_allreduce_wire_bytes_ratio",
        "model": (f"25M-param dense Adam DP, 8-dev, threshold scheme @ "
                  f"target 1e-3 (wire {r['wire_bytes']:.0f} B vs dense "
                  f"{r['dense_bytes']:.0f} B; adapted threshold "
                  f"{r['threshold']:.2e}; CPU step A/B compressed "
                  f"{r['compressed_step_seconds']:.3f}s vs exact "
                  f"{r['exact_step_seconds']:.3f}s — CPU cannot rank, "
                  f"encode costs FLOPs here while wire savings pay on DCN)"),
        "value": round(r["ratio"], 6),
        "unit": "fraction",
        "vs_baseline": round(r["ratio"] / 0.1, 4),  # <= 0.1 acceptance
    }


_TP_BERT_CHILD = r"""
import json, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from jax.sharding import PartitionSpec as P
from deeplearning4j_tpu.data import ArrayDataSetIterator
from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMesh
from deeplearning4j_tpu.zoo.bert import Bert

B, SEQ = 32, 64
model = Bert.tiny(task="classification", num_classes=2, max_length=SEQ)
net = model.init()
mesh = TrainingMesh(data=4, model=2)
# Megatron-style annotation (SNIPPETS.md [3]): attention QKV + FFN-in are
# column-sharded, the output projections row-sharded; everything else
# (embeddings, norms, head) stays replicated — XLA inserts the TP
# collectives from the annotations alone
net.params = mesh.tensor_shard_params(net.params, [
    (r"\['W[qkv]'\]$", P(None, "model")),
    (r"\['Wo'\]$", P("model", None)),
    (r"\['W1'\]$", P(None, "model")),
    (r"\['W2'\]$", P("model", None)),
])
rng = np.random.default_rng(0)
tok = rng.integers(0, model.vocab_size, size=(B, SEQ))
seg = np.zeros((B, SEQ))
x = np.stack([tok, seg], axis=-1).astype(np.int32)
y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, size=B)]
it = ArrayDataSetIterator(x, y, batch=B)
pw = ParallelWrapper(net, mesh=mesh, skew_every=0)
pw.fit(it, epochs=1)  # compile
steps = 6
t0 = time.perf_counter()
pw.fit(it, epochs=steps)
jax.block_until_ready(jax.tree_util.tree_leaves(net.params)[0])
dt = time.perf_counter() - t0
n_tp = sum(1 for v in jax.tree_util.tree_leaves(net.params)
           if hasattr(v, "sharding") and any(getattr(v.sharding, "spec", ()) or ()))
print(json.dumps({"samples_per_sec": B * steps / dt, "tp_sharded_leaves": n_tp}))
"""


def bench_tp_bert_smoke():
    """Tensor-parallel smoke on the ("data","model") 2-D mesh — the new
    axis gets a number from day one. BERT (CPU-sized tiny config; the same
    annotation rules apply to base on the chip) with Megatron-style
    NamedSharding on QKV/FFN kernels, 4x2 virtual-device mesh."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _TP_BERT_CHILD], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.abspath(__file__)))
    line = [l for l in out.stdout.strip().splitlines()
            if l.startswith("{")][-1]
    r = json.loads(line)
    if r["tp_sharded_leaves"] == 0:
        raise RuntimeError("no tensor-parallel leaves were sharded")
    return {
        "metric": "tp_bert_smoke_samples_per_sec",
        "model": (f"zoo.bert.Bert.tiny B=32 seq=64 on (data=4, model=2) "
                  f"virtual CPU mesh, {r['tp_sharded_leaves']} TP-sharded "
                  "param leaves"),
        "value": round(r["samples_per_sec"], 2),
        "unit": "samples/sec",
        "vs_baseline": None,  # first number on this axis
    }


def bench_attention_2k(batch: int = 4, seq: int = 2048, k_lo: int = 8,
                       k_hi: int = 40):
    """Extra metric (VERDICT r2 #5): seq-2048 flash-attention fwd+bwd token
    throughput — the regime the blockwise kernel is for (ops/attention.py
    FLASH_MIN_SEQ). TWO-POINT FIT: time K-iteration scans at two K inside
    one jit each and take (wall(K_hi) - wall(K_lo)) / (K_hi - K_lo),
    cancelling the fixed per-call dispatch and fetch cost that a
    single-call timing would fold into every iteration."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.attention import flash_attention

    H, D = 12, 64
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(
        rng.normal(size=(batch, H, seq, D)).astype(np.float32)
    ).astype(jnp.bfloat16)
    q, k, v = mk(), mk(), mk()

    def loss(q, k, v, s):
        return jnp.sum(flash_attention(q + s, k, v).astype(jnp.float32))

    g = jax.value_and_grad(loss, argnums=(0, 1, 2))

    def make_many(iters):
        @jax.jit
        def many(q, k, v):
            def body(c, s):
                val, grads = g(q, k, v, s.astype(jnp.bfloat16))
                return c + val + sum(jnp.sum(x).astype(jnp.float32)
                                     for x in grads), None

            out, _ = jax.lax.scan(
                body, jnp.float32(0),
                jnp.arange(iters, dtype=jnp.float32) * 1e-6)
            return out
        return many

    def timed(fn):
        float(fn(q, k, v))  # compile + warm
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            float(fn(q, k, v))
            best = min(best, time.perf_counter() - t0)
        return best

    lo_fn, hi_fn = make_many(k_lo), make_many(k_hi)

    def one_fit():
        for _ in range(3):  # jitter can make t_hi <= t_lo; retry, never clamp
            t_lo = timed(lo_fn)
            t_hi = timed(hi_fn)
            if t_hi > t_lo:
                return (t_hi - t_lo) / (k_hi - k_lo)
        raise RuntimeError(
            f"two-point fit invalid after retries (t_lo={t_lo:.4f}s >= "
            f"t_hi={t_hi:.4f}s): session latency noise exceeds the "
            "device-time delta; not reporting a corrupted number")

    dt, noise = _med3(one_fit)
    return {
        "metric": "flash_attention_seq2048_tokens_per_sec",
        "model": f"flash fwd+bwd B={batch} H={H} S={seq} D={D} bf16",
        "value": round(batch * seq / dt),
        "noise": noise,
        "unit": "tokens/sec",
        "vs_baseline": None,  # no reference number exists
    }


def bench_lstm_char_rnn(batch: int = 128, seq: int = 128, vocab: int = 96,
                        hidden: int = 512, steps: int = 60):
    """Tracked metric 4 (BASELINE config #3): GravesLSTM-class char-RNN
    train-step tokens/sec — 2xLSTM(H) + RnnOutputLayer, one-hot inputs,
    bf16. Methodology: many steps in flight, completion forced by the final
    score fetch (the per-step dispatch pipeline amortizes the per-call
    latency; 7.87 ms/step device time from the XPlane trace at this
    config, r4, 2026-07)."""
    import jax

    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.recurrent import LSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
            .compute_dtype("bfloat16").list()
            .layer(LSTM(n_in=vocab, n_out=hidden))
            .layer(LSTM(n_in=hidden, n_out=hidden))
            .layer(RnnOutputLayer(n_in=hidden, n_out=vocab))
            .set_input_type(InputType.recurrent(vocab, seq))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = jax.device_put(np.eye(vocab, dtype=np.float32)[
        rng.integers(0, vocab, (batch, seq))])
    y = jax.device_put(np.eye(vocab, dtype=np.float32)[
        rng.integers(0, vocab, (batch, seq))])
    for _ in range(4):
        net._fit_batch(x, y)
    float(net.score_value)

    def one_run():
        t0 = time.perf_counter()
        for _ in range(steps):
            net._fit_batch(x, y)
        float(net.score_value)
        return (time.perf_counter() - t0) / steps

    dt, noise = _med3(one_run)
    return {
        "metric": "lstm_char_rnn_train_tokens_per_sec",
        "model": f"2xLSTM(H={hidden}) char-RNN B={batch} T={seq} V={vocab} bf16",
        "value": round(batch * seq / dt),
        "noise": noise,
        "unit": "tokens/sec",
        "vs_baseline": None,  # no reference number exists
    }


def _build_lenet(seed: int = 0, sync_every: int = 1):
    """LeNet-5 MNIST on the nn DSL, zoo-independent (shared by the fallback
    metric and the host-pipeline overlap metric)."""
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import (ConvolutionLayer, DenseLayer,
                                              OutputLayer, SubsamplingLayer)
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (
        NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
        .sync_every(sync_every).list()
        .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5),
                                padding="VALID", activation="relu"))
        .layer(SubsamplingLayer(kernel_size=(2, 2)))
        .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5),
                                padding="VALID", activation="relu"))
        .layer(SubsamplingLayer(kernel_size=(2, 2)))
        .layer(DenseLayer(n_out=500, activation="relu"))
        .layer(OutputLayer(n_in=500, n_out=10))
        .set_input_type(InputType.convolutional(28, 28, 1))
        .build()
    )
    return MultiLayerNetwork(conf).init()


class _SlowIterator:
    """DataSetIterator facade injecting a fixed ETL delay per batch — the
    A/B load for the host-pipeline overlap metric (sleep-based = I/O-shaped
    ETL; a CPU-bound transform could not overlap on this 1-core host —
    docs/HOST_PIPELINE.md measurement-ceiling note)."""

    def __init__(self, base, delay_s: float):
        self.base = base
        self.delay_s = delay_s

    def __iter__(self):
        for ds in self.base:
            time.sleep(self.delay_s)
            yield ds

    def reset(self):
        if hasattr(self.base, "reset"):
            self.base.reset()

    def batch_size(self):
        return self.base.batch_size()


def bench_host_pipeline(batch: int = 64, n_batches: int = 12):
    """host_pipeline_overlap: LeNet-5 fit wall-time under an injected slow
    transform divided by compute-only wall-time. Serial feeding pays
    compute + ETL per step (ratio ≈ 2× when the injected delay equals the
    step time); the device-prefetch iterator (AsyncDataSetIterator,
    sync_every>1 orchestration) overlaps ETL + device_put of batch k+1 under
    batch k's compute — target ≤ 1.15×. Median-of-3 on the RATIOS with the
    standard noise field; the serial ratio is reported alongside so both
    ends of the A/B are in the table (ISSUE 2 acceptance)."""
    import jax

    from deeplearning4j_tpu.data import (ArrayDataSetIterator,
                                         AsyncDataSetIterator)

    net = _build_lenet(sync_every=max(2, n_batches // 2))

    class _Observer:  # a listener must be installed for the coalesced
        count = 0     # dispatch path to be IN the measured loop (with no
                      # listeners the dispatcher skips the fetch entirely)
        def iteration_done(self, model, iteration, epoch):
            self.count += 1

    net.set_listeners(_Observer())
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch * n_batches, 28, 28, 1)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, len(x))]
    xd, yd = jax.device_put(x[:batch]), jax.device_put(y[:batch])
    for _ in range(4):  # warm past every recompile
        net._fit_batch(xd, yd)
    float(net.score_value)

    def compute_only():
        t0 = time.perf_counter()
        for _ in range(n_batches):
            net._fit_batch(xd, yd)
        float(net.score_value)
        return time.perf_counter() - t0

    t_step = compute_only() / n_batches
    # 0.8x a step of compute: heavy enough that serial feeding pays ~1.8-2x,
    # light enough that a working overlap can actually hide it — at exactly
    # 1.0x the pipeline is critically balanced and every ms of worker/queue
    # overhead lands in the ratio instead of under the compute
    delay = 0.8 * t_step

    def fit_wall(iterator):
        t0 = time.perf_counter()
        net.fit(iterator, epochs=1)
        float(net.score_value)
        return time.perf_counter() - t0

    def one_run():
        it = lambda: ArrayDataSetIterator(x, y, batch=batch)  # noqa: E731
        t_c = compute_only()
        t_serial = fit_wall(_SlowIterator(it(), delay))
        t_pref = fit_wall(AsyncDataSetIterator(_SlowIterator(it(), delay),
                                               buffer_size=2))
        return t_pref / t_c, t_serial / t_c

    runs = sorted(one_run() for _ in range(3))
    overlap = runs[1][0]
    serial = sorted(r[1] for r in runs)[1]
    spread = (runs[-1][0] - runs[0][0]) / 2.0 / overlap if overlap else 0.0
    return {
        "metric": "host_pipeline_overlap",
        "model": (f"LeNet-5 B={batch} x{n_batches} batches, injected ETL "
                  f"{delay * 1e3:.1f} ms/batch (0.8x step), prefetch "
                  "buffer=2, coalesced sync"),
        "value": round(overlap, 4),
        "noise": f"±{round(100 * spread, 1)}% (3-sample spread/2)",
        "unit": "x compute-only wall (1.0 = ETL fully hidden)",
        "serial_ratio": round(serial, 4),  # the no-prefetch end of the A/B
        # ≤ 1.0 means the ≤1.15x overlap target is met
        "vs_baseline": round(overlap / 1.15, 4),
    }


def bench_telemetry_overhead(batch: int = 64, steps: int = 30):
    """telemetry_overhead: steady-state step time with the FULL observability
    stack on (telemetry spans + step histogram, TrainingHealthMonitor with
    NaN sentinel/update-ratio probe, RecompileListener, coalesced dispatch)
    over step time with telemetry disabled and no listeners — the price of
    watching (docs/OBSERVABILITY.md). Target ≤ 1.05x (ISSUE 4 acceptance).
    Median-of-3 of the ratio with the standard noise field."""
    import jax

    from deeplearning4j_tpu.nn.listeners import RecompileListener
    from deeplearning4j_tpu.util import telemetry as tm
    from deeplearning4j_tpu.util.health import TrainingHealthMonitor

    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 28, 28, 1)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=batch)]
    net = _build_lenet(sync_every=4)
    xd, yd = jax.device_put(x), jax.device_put(y)
    tele = tm.get_telemetry()

    def timed(enable):
        tele.enabled = enable
        if enable:
            net.set_listeners(TrainingHealthMonitor(window=4, log_fn=None),
                              RecompileListener(log_fn=lambda *a: None))
        else:
            net.set_listeners()
        # warm past recompiles AND two window=4 boundaries, so both probe
        # variants (first-window no-prev and steady with-prev) have traced
        # and compiled before the timed region
        for _ in range(8):
            net._fit_batch(xd, yd)
        net._dispatcher.flush()
        float(net.score_value)
        t0 = time.perf_counter()
        for _ in range(steps):
            net._fit_batch(xd, yd)
        net._dispatcher.flush()
        float(net.score_value)
        return (time.perf_counter() - t0) / steps

    was_enabled = tele.enabled
    try:
        def one_ratio():
            t_off = timed(False)
            t_on = timed(True)
            return t_on / t_off

        ratio, noise = _med3(one_ratio)
    finally:
        tele.enabled = was_enabled
        net.set_listeners()
    return {
        "metric": "telemetry_overhead",
        "model": (f"LeNet-5 B={batch} x{steps} steps, spans + health monitor"
                  " (window=4 NaN sentinel/update-ratio probe) +"
                  " RecompileListener + coalesced dispatch, on vs off"),
        "value": round(ratio, 4),
        "noise": noise,
        "unit": "x untelemetered step time (1.0 = free)",
        # ≤ 1.0 means the ≤ 1.05x overhead target is met
        "vs_baseline": round(ratio / 1.05, 4),
    }


def bench_cost_attribution(batch: int = 64, steps: int = 30):
    """cost_attribution_overhead: steady-state step time with cost
    attribution ENABLED (a computed+published CostReport priming the
    per-step examples_per_sec / model_flops_utilization gauges, telemetry
    on) over step time with plain telemetry and no attribution — the
    per-step price of knowing where the FLOPs go
    (docs/OBSERVABILITY.md#cost-attribution--mfu). The one-time static
    analysis (lower+compile+HLO parse) runs OUTSIDE the timed region — it
    is a startup cost, reported separately as ``analysis_seconds``. Target
    <= 1.05x; median-of-3 with the standard noise field. Also reports the
    attribution-reconciliation ratio (per-layer FLOPs summed over the XLA
    whole-program total — the tests pin it within 5%)."""
    import jax

    from deeplearning4j_tpu.util import telemetry as tm

    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 28, 28, 1)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[np.random.default_rng(1).integers(
        0, 10, size=batch)]
    net = _build_lenet()
    xd, yd = jax.device_put(x), jax.device_put(y)
    tele = tm.get_telemetry()
    was_enabled = tele.enabled
    tele.enabled = True

    def timed():
        for _ in range(6):  # warm past every recompile
            net._fit_batch(xd, yd)
        float(net.score_value)
        t0 = time.perf_counter()
        for _ in range(steps):
            net._fit_batch(xd, yd)
        float(net.score_value)
        return (time.perf_counter() - t0) / steps

    try:
        t_an = time.perf_counter()
        # attribution on: published report + an explicit peak so the MFU
        # gauge branch is exercised even without DL4J_TPU_PEAK_FLOPS set
        report = net.cost_report(batch_size=batch, peak_flops=1e12)
        analysis_seconds = time.perf_counter() - t_an
        attributed = sum(r.flops for r in report.rows)
        recon = attributed / report.flops_per_step \
            if report.flops_per_step else None

        def one_ratio():
            # attribution off: same net, gauges disarmed
            net._cost_flops_per_example = None
            net._peak_flops = None
            t_off = timed()
            net._cost_flops_per_example = report.flops_per_step / batch
            net._peak_flops = 1e12
            t_on = timed()
            return t_on / t_off

        ratio, noise = _med3(one_ratio)
    finally:
        tele.enabled = was_enabled
    return {
        "metric": "cost_attribution_overhead",
        "model": (f"LeNet-5 B={batch} x{steps} steps, per-step "
                  "examples/sec + MFU gauges from a published CostReport, "
                  "on vs off (telemetry on both sides)"),
        "value": round(ratio, 4),
        "noise": noise,
        "unit": "x unattributed step time (1.0 = free)",
        "analysis_seconds": round(analysis_seconds, 3),
        "attribution_source": report.source,
        # per-layer FLOPs summed / XLA whole-program total (1.0 = exact)
        "flops_reconciliation": round(recon, 4) if recon else None,
        # <= 1.0 means the <= 1.05x overhead target is met
        "vs_baseline": round(ratio / 1.05, 4),
    }


def bench_optimizer_update_share(depth: int = 96, width: int = 8,
                                 batch: int = 32, steps: int = 5):
    """optimizer_update_ms_share: the update phase's fraction of attributed
    per-step device time (the ``(optimizer)`` cost-attribution row from a
    profiled ``cost_report()``, docs/OBSERVABILITY.md) with the FUSED
    donated optimizer apply (docs/KERNELS.md#fused-optimizer-apply) on the
    many-leaf workload the per-leaf walk is worst at — a deep narrow Adam
    MLP (2*depth+3 param leaves). LOWER_BETTER, gated by
    benchmarks/regression_gate.py.

    Honesty (r6 convention — the full A/B rides in the record): on
    XLA:CPU the per-leaf update ops FUSE INTO the backward kernels, so the
    per-leaf ``(optimizer)`` row undercounts its true cost and the two
    *shares* are not directly comparable; what IS directly comparable is
    the whole-step wall time, reported as ``fused_step_ms`` /
    ``per_leaf_step_ms`` (measured here: the fused apply makes the WHOLE
    step ~2.4x faster at this config by collapsing ~200 tiny update ops
    into a handful of buffer ops). The gated value is the fused share —
    self-consistent run to run, it keeps the fused update phase from
    regressing. Median-of-3 with the standard noise field."""
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam

    def build(fused):
        b = NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
        if fused:
            b = b.fused_update(True)
        lb = b.list()
        for _ in range(depth):
            lb = lb.layer(DenseLayer(n_in=width, n_out=width,
                                     activation="relu"))
        lb = lb.layer(OutputLayer(n_in=width, n_out=8))
        conf = lb.set_input_type(InputType.feed_forward(width)).build()
        return MultiLayerNetwork(conf).init()

    def measure(fused):
        net = build(fused)
        rep = net.cost_report(batch_size=batch, profile=True, steps=steps,
                              publish=False)
        s = rep.optimizer_update_share
        if s is None:
            raise RuntimeError(
                "no profiled device-time attribution on this backend — "
                "optimizer_update_ms_share cannot be measured honestly")
        return s, rep.step_time_s * 1e3

    # ONE set of 3 runs per config; share and step-ms medians come from it
    fused_runs = sorted(measure(True) for _ in range(3))
    per_leaf_runs = sorted(measure(False) for _ in range(3))
    fused_share = sorted(r[0] for r in fused_runs)[1]
    per_leaf_share = sorted(r[0] for r in per_leaf_runs)[1]
    fused_ms = sorted(r[1] for r in fused_runs)[1]
    per_leaf_ms = sorted(r[1] for r in per_leaf_runs)[1]
    spread = (fused_runs[-1][0] - fused_runs[0][0]) / 2.0 / fused_share \
        if fused_share else 0.0
    noise = f"±{round(100 * spread, 1)}% (3-sample spread/2)"
    return {
        "metric": "optimizer_update_ms_share",
        "model": (f"deep-narrow Adam MLP depth={depth} width={width} "
                  f"B={batch} ({2 * depth + 3} param leaves), fused "
                  "dtype-grouped resident-buffer apply"),
        "value": round(fused_share, 4),
        "noise": noise,
        "unit": "fraction of attributed device time (LOWER_BETTER)",
        # the honest A/B (per-leaf share undercounts: its update ops fuse
        # into backward kernels on XLA:CPU — see docstring):
        "per_leaf_share": round(per_leaf_share, 4),
        "fused_step_ms": round(fused_ms, 3),
        "per_leaf_step_ms": round(per_leaf_ms, 3),
        # whole-step win of the fused apply at this config (< 1 = faster)
        "vs_baseline": round(fused_ms / per_leaf_ms, 4) if per_leaf_ms
        else None,
    }


def bench_autotune_dispatch(batch: int = 8, calls: int = 150):
    """autotune_dispatch_overhead: per-call time of an eager
    ``kernel_impl=auto`` conv2d whose dispatch CONSULTS the tuning
    database (DL4J_TPU_TUNING_DB armed, a committed winner for this exact
    geometry — tuning/database.py, docs/AUTOTUNE.md) over the hardwired
    ``exact``-pinned dispatch running the identical executable. The
    committed winner IS ``exact``, so both paths execute the same conv —
    the ratio isolates what the database consultation costs at trace/
    dispatch time: one signature f-string + one in-memory-cached lookup.
    Target ≤ 1.05x, wired LOWER_BETTER into benchmarks/regression_gate.py
    (ISSUE 11 acceptance). Median-of-3 with the standard noise field."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import tuning
    from deeplearning4j_tpu.ops import kernels as K
    from deeplearning4j_tpu.ops import nn as nnops
    from deeplearning4j_tpu.ops.kernels import conv as kconv

    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(
        rng.normal(size=(batch, 16, 16, 8)), jnp.float32))
    w = jax.device_put(jnp.asarray(
        rng.normal(size=(3, 3, 8, 16)) * 0.1, jnp.float32))
    sig = kconv.shape_signature(x.shape, w.shape, (1, 1), "SAME", (1, 1), 1)
    db_dir = tempfile.mkdtemp(prefix="dl4j-bench-tuning.")
    db = tuning.set_database(db_dir)
    # a committed exact winner: the DB-consulted path must resolve to the
    # SAME executable as the hardwired path, so the ratio is pure dispatch
    db.commit(tuning.TuningKey.for_op("conv2d", sig, "float32"),
              {"winner": {"label": "exact", "impl": "exact", "params": {},
                          "ms": 0.0, "noise": "n/a"},
               "candidates_digest": "bench-direct-commit",
               "measured": []})

    def timed(scope):
        with K.impl_scope(scope):
            jax.block_until_ready(nnops.conv2d(x, w))   # warm + compile
            t0 = time.perf_counter()
            for _ in range(calls):
                out = nnops.conv2d(x, w)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / calls

    try:
        def one_ratio():
            # min-of-3 per scope inside each sample: the dispatch delta
            # being measured is ~µs against a ~250µs eager call, so the
            # minimum (least scheduler interference) is the stable
            # estimator; the outer median-of-3 still reports honest noise
            t_exact = min(timed("exact") for _ in range(3))
            t_auto = min(timed("auto") for _ in range(3))
            return t_auto / t_exact

        ratio, noise = _med3(one_ratio)
    finally:
        tuning.set_database(None)
        shutil.rmtree(db_dir, ignore_errors=True)
    return {
        "metric": "autotune_dispatch_overhead",
        "model": (f"eager conv2d B={batch} 16x16x8->16 x{calls} calls, "
                  "auto dispatch through a committed tuning-DB winner "
                  "(=exact) vs impl_scope('exact') hardwired"),
        "value": round(ratio, 4),
        "noise": noise,
        "unit": "x hardwired dispatch time (1.0 = free)",
        # ≤ 1.0 means the ≤ 1.05x overhead target is met
        "vs_baseline": round(ratio / 1.05, 4),
    }


def bench_elastic_overhead(batch: int = 64, steps: int = 40):
    """elastic_overhead: steady-state step time under full ElasticTrainer
    supervision — live heartbeat thread (FileMembership, 100ms cadence),
    periodic ASYNC checkpointing (a commit landing inside the timed
    window), drain-signal handling, and the rollback health monitor — over
    bare fit() step time (docs/FAULT_TOLERANCE.md). Step time is measured
    between the FIRST and LAST iteration_done timestamps of one epoch, so
    the one-time blocking commits at the run's edges (the initial rollback
    target, the final drain save) count as startup/shutdown — reported
    separately as ``checkpoint_seconds`` (the r10 ``analysis_seconds``
    convention) — while the per-step supervision and the in-window async
    commit are exactly what the ratio prices. Target <= 1.05x (ISSUE 6
    acceptance); median-of-3 with the standard noise field."""
    import shutil
    import tempfile

    from deeplearning4j_tpu.data import ArrayDataSetIterator
    from deeplearning4j_tpu.nn.listeners import TrainingListener
    from deeplearning4j_tpu.parallel import ElasticTrainer, FileMembership
    from deeplearning4j_tpu.util.checkpoint import ShardedCheckpointer

    from deeplearning4j_tpu.util.health import TrainingHealthMonitor

    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch * steps, 28, 28, 1)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=batch * steps)]
    it = lambda: ArrayDataSetIterator(x, y, batch=batch)  # noqa: E731
    net = _build_lenet()
    # ONE monitor shared by every supervised run, warmed here so its jitted
    # NaN-sentinel/update-ratio probes compile outside the timed window
    # (its per-step cost is already priced by telemetry_overhead; what this
    # bench adds on top is heartbeats + checkpointing + supervision)
    monitor = TrainingHealthMonitor(action="rollback", window=10, log_fn=None)
    net.listeners.append(monitor)
    net.fit(it(), epochs=1)  # compile step + both probe variants
    net.listeners.remove(monitor)

    class _Stamps(TrainingListener):
        def __init__(self):
            self.t = []

        def iteration_done(self, model, iteration, epoch):
            # forces the loss fetch (score_value float) like a real
            # listener window boundary would — same cost on both sides
            self.t.append(time.perf_counter())

    work_dir = tempfile.mkdtemp(prefix="dl4j-elastic-bench-")
    try:
        # the run-edge blocking commit, reported separately (startup cost)
        ck = ShardedCheckpointer(os.path.join(work_dir, "probe"), log_fn=None)
        t0 = time.perf_counter()
        ck.save(0, net)
        checkpoint_seconds = time.perf_counter() - t0

        def steady(dts):
            assert len(dts) >= 2
            return (dts[-1] - dts[0]) / (len(dts) - 1)

        def t_plain():
            stamps = _Stamps()
            net.listeners.append(stamps)
            try:
                net.fit(it(), epochs=1)
            finally:
                net.listeners.remove(stamps)
            return steady(stamps.t)

        run = [0]

        def t_elastic():
            run[0] += 1
            stamps = _Stamps()
            net.listeners.append(stamps)
            membership = FileMembership(
                os.path.join(work_dir, f"members-{run[0]}"), process_id=0,
                world_size=1, heartbeat_interval=0.1, log_fn=None)
            trainer = ElasticTrainer(
                net, os.path.join(work_dir, f"ck-{run[0]}"),
                checkpoint_every=max(1, steps // 3),  # async commits inside
                membership=membership, monitor=monitor, log_fn=None)
            try:
                trainer.fit(it(), epochs=net.epoch + 1)
            finally:
                net.listeners.remove(stamps)
            assert trainer.state == "completed", trainer.state
            return steady(stamps.t)

        def one_ratio():
            return t_elastic() / t_plain()

        ratio, noise = _med3(one_ratio)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    return {
        "metric": "elastic_overhead",
        "model": (f"LeNet-5 B={batch} x{steps} steps under ElasticTrainer "
                  "(100ms heartbeats + async checkpoint every "
                  f"{max(1, steps // 3)} steps + rollback monitor + drain "
                  "handler) vs bare fit()"),
        "value": round(ratio, 4),
        "noise": noise,
        "unit": "x unsupervised step time (1.0 = free)",
        # one-time blocking rollback-target commit (startup, not per-step)
        "checkpoint_seconds": round(checkpoint_seconds, 3),
        # <= 1.0 means the <= 1.05x overhead target is met
        "vs_baseline": round(ratio / 1.05, 4),
    }


_RECOMPILE_CHILD = r"""
import json, sys, time
T0 = time.perf_counter()   # process-start reference for cold-start wall
import jax
jax.config.update("jax_platforms", "cpu")
import os
cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")  # parent places it
if cache_dir:
    from deeplearning4j_tpu.util.compile_cache import enable_persistent_cache
    enable_persistent_cache()
import numpy as np
from deeplearning4j_tpu.util import get_watcher

w = get_watcher()   # install monitoring hooks BEFORE any compile happens
from deeplearning4j_tpu.zoo import ResNet50

# flagship topology, CPU-sized (the scaling child's config: same graph and
# collective structure as 224px, small enough for the 1-core host)
net = ResNet50(num_classes=16, input_shape=(32, 32, 3)).init()
if cache_dir:
    # full compile-once chain: the AOT lowering store (skips the warm
    # process's Python trace + MLIR build) on top of the persistent cache
    # (skips its backend compile) — docs/COMPILE_CACHE.md
    net.warmup(shapes=[(8, 32, 32, 3)], inference=False,
               export_dir=os.path.join(cache_dir, "aot"))
rng = np.random.default_rng(0)
x = jax.device_put(rng.normal(size=(8, 32, 32, 3)).astype(np.float32))
y = jax.device_put(np.eye(16, dtype=np.float32)[rng.integers(0, 16, 8)])
step_walls = []
t_first_done = None
for _ in range(6):
    t0 = time.perf_counter()
    net._fit_batch(x, y)
    float(net.score_value)   # completion fence per step (wall attribution)
    step_walls.append(time.perf_counter() - t0)
    if t_first_done is None:
        t_first_done = time.perf_counter()
# first stable step: first index whose wall is within 2x the best tail step
floor = min(step_walls[1:])
stable_at = next(i for i, t in enumerate(step_walls) if t <= 2 * floor)
print(json.dumps({
    "cold_start_s": round(t_first_done - T0, 3),  # launch -> first step done
    "first_step_s": round(step_walls[0], 3),
    "steady_step_s": round(floor, 4),
    "steps_to_stable": stable_at,
    "backend_compiles": w.backend_compiles,
    "persistent_cache_hits": w.persistent_cache_hits,
}))
"""


def bench_recompile_overhead(runs: int = 3):
    """recompile_overhead: warm-persistent-cache cold-PROCESS start over the
    uncached cold start, on the flagship-topology CPU-sized model (ResNet-50
    32px — the scaling child's config). Each sample spawns two child
    processes against one fresh ``compilation_cache_dir``: the first pays
    every XLA compile (and populates the cache), the second deserializes.
    Cold start = process launch to first completed train step. Target:
    warm/cold <= 0.5; median-of-{runs} with the standard
    ``noise`` field. Also reports the ragged-tail compile-count A/B (0 extra
    traces bucketed vs >= 1 unbucketed) measured in-process."""
    import shutil
    import tempfile

    def child(cache_dir):
        # scrub inherited knobs: an ambient compile-cache or bucketing env
        # var would corrupt the cold/uncached baseline. The throwaway cache
        # of this CPU cold/warm experiment is placed the one way the
        # program allows — from outside, through JAX's own variable.
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("DL4J_TPU_")
               and k != "JAX_COMPILATION_CACHE_DIR"}
        env["JAX_PLATFORMS"] = "cpu"
        if cache_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        out = subprocess.run(
            [sys.executable, "-c", _RECOMPILE_CHILD],
            env=env, capture_output=True, text=True, timeout=900,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        line = [l for l in out.stdout.strip().splitlines()
                if l.startswith("{")][-1]
        return json.loads(line)

    pairs = []

    def one_ratio():
        td = tempfile.mkdtemp(prefix="dl4j_cc_bench_")
        try:
            cold = child(td)   # empty dir: every compile is real + persisted
            warm = child(td)   # same dir, fresh process: deserialize
        finally:
            shutil.rmtree(td, ignore_errors=True)
        r = warm["cold_start_s"] / cold["cold_start_s"]
        pairs.append((r, cold, warm))
        return r

    ratio, noise = _med3(one_ratio, runs=runs)
    # every reported companion figure comes from the MEDIAN-ratio sample —
    # not run order — so the record is one internally consistent run
    _, cold_med_run, warm_med_run = sorted(
        pairs, key=lambda p: p[0])[len(pairs) // 2]
    cold_med = cold_med_run["cold_start_s"]
    warm_med = warm_med_run["cold_start_s"]
    bucketed, unbucketed = _ragged_tail_traces()
    return {
        "metric": "recompile_overhead",
        "model": ("zoo.ResNet50 32px classes=16 B=8 fp32 (flagship topology,"
                  " CPU-sized); persistent XLA cache + AOT lowering store,"
                  " cold vs warm process"),
        "value": round(ratio, 4),
        "noise": noise,
        "unit": "x uncached cold-process start (lower is better)",
        "cold_start_s": cold_med,
        "warm_start_s": warm_med,
        "warm_cache_hits": warm_med_run["persistent_cache_hits"],
        "steps_to_stable_cold": cold_med_run["steps_to_stable"],
        # ragged-tail epoch (N % B != 0): extra train-step traces beyond the
        # first — 0 under bucketing, >= 1 without (compile_cache_sweep.py
        # demonstrates the same on full epochs)
        "ragged_extra_traces_bucketed": bucketed,
        "ragged_extra_traces_unbucketed": unbucketed,
        # <= 1.0 means the <= 0.5x warm-start target is met
        "vs_baseline": round(ratio / 0.5, 4),
    }


def _ragged_tail_traces():
    """(bucketed, unbucketed) EXTRA train-step traces for a ragged-tail
    epoch (beyond the one expected full-batch compile)."""
    import numpy as np

    from deeplearning4j_tpu.data import ArrayDataSetIterator
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.util import get_watcher

    def run(buckets):
        # explicit on both axes so an ambient DL4J_TPU_BUCKETS can never
        # bucket the "unbucketed" baseline of this A/B
        b = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
             .batch_buckets(buckets).seq_buckets(None))
        conf = (b.list()
                .layer(DenseLayer(n_in=16, n_out=32, activation="relu"))
                .layer(OutputLayer(n_in=32, n_out=10))
                .set_input_type(InputType.feed_forward(16)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(20, 16)).astype(np.float32)  # 20 % 8 = 4 ragged
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 20)]
        w = get_watcher()
        with w.scope() as s:
            net.fit(ArrayDataSetIterator(x, y, batch=8), epochs=2)
            return s.traces_of("MultiLayerNetwork.train_step") - 1
    return run((8,)), run(None)


def bench_serving(classify_requests: int = 48, generate_requests: int = 4,
                  max_new_tokens: int = 6):
    """serving_p99_latency_ms + serving_qps: the serving tier end-to-end at
    the scheduler level (benchmarks/serving_smoke.py covers the HTTP hop;
    gating below HTTP keeps socket scheduling noise out of the bands).
    Mixed two-model multi-tenant workload (docs/SERVING.md): LeNet classify
    requests on the interactive lane of one model + BERT-tiny KV-cache
    decode requests on the batch lane of ANOTHER model, each with its own
    scheduler. All bucket executables are warmed before the timed region
    and the record carries the steady-state ``serving.recompiles_total``
    delta (must be 0) plus a batched-vs-sequential bit-identity probe —
    the ISSUE 8 acceptance facts ride in the BENCH record itself.
    p99 is the exact quantile over every request's submit→complete latency;
    QPS is completed requests over the wall time to full drain. Both
    median-of-3 with the standard noise field."""
    import threading

    from deeplearning4j_tpu.data.bucketing import BucketingPolicy
    from deeplearning4j_tpu.serving import ModelRouter, ServingModel
    from deeplearning4j_tpu.util import telemetry as tm
    from deeplearning4j_tpu.zoo.bert import Bert

    lenet = _build_lenet()
    clf = ServingModel(lenet, "lenet", bucketing=BucketingPolicy(
        batch_buckets=(1, 2, 4, 8)))
    bert = Bert.tiny(causal=True, task="mlm", vocab_size=64, max_length=32,
                     hidden_dropout=0.0).init()
    gen = ServingModel(bert, "bert-tiny-decode", kind="generate",
                       bucketing=BucketingPolicy(batch_buckets=(1, 2, 4),
                                                 seq_buckets=(8,)))
    router = ModelRouter(name="bench")
    router.register(clf, max_wait_ms=1.0, queue_limit=256)
    router.register(gen, max_wait_ms=1.0, queue_limit=256)
    router.warmup()

    rng = np.random.default_rng(0)
    images = rng.normal(size=(8, 28, 28, 1)).astype(np.float32)
    prompts = [list(rng.integers(1, 64, size=5)) for _ in range(4)]

    def one_run():
        lat, lock = [], threading.Lock()
        t_end = [0.0]

        def cb(ts):
            def _done(f):
                now = time.perf_counter()
                with lock:
                    lat.append(now - ts)
                    t_end[0] = max(t_end[0], now)
            return _done

        t0 = time.perf_counter()
        futs = []
        for i in range(generate_requests):
            ts = time.perf_counter()
            f = router.submit("bert-tiny-decode",
                              np.asarray(prompts[i % len(prompts)],
                                         np.int32),
                              lane="batch", max_new_tokens=max_new_tokens)
            f.add_done_callback(cb(ts))
            futs.append(f)
        for i in range(classify_requests):
            ts = time.perf_counter()
            f = router.submit("lenet", images[i % 8][None],
                              lane="interactive")
            f.add_done_callback(cb(ts))
            futs.append(f)
        for f in futs:
            f.result(timeout=300)
        # result() can wake before the done-callbacks have stamped (Future
        # notifies waiters, then invokes callbacks) — wait for every stamp
        # so p99/QPS cover the full sample set
        deadline = time.perf_counter() + 30.0
        while time.perf_counter() < deadline:
            with lock:
                if len(lat) == len(futs):
                    break
            time.sleep(1e-3)
        wall = t_end[0] - t0
        lat.sort()
        p99 = lat[min(len(lat) - 1, int(round(0.99 * (len(lat) - 1))))]
        return p99 * 1e3, len(lat) / wall

    one_run()  # steady-state entry: every signature warm before measuring
    tele = tm.get_telemetry()
    rec_key = lambda: sum(  # noqa: E731
        v for (name, _l), v in tele.counters.items()
        if name == "serving.recompiles_total")
    rec_before = rec_key()
    runs = sorted(one_run() for _ in range(3))
    steady_recompiles = rec_key() - rec_before
    p99s = sorted(r[0] for r in runs)
    qpss = sorted(r[1] for r in runs)
    p99, qps = p99s[1], qpss[1]
    p99_noise = (p99s[-1] - p99s[0]) / 2.0 / p99 if p99 else 0.0
    qps_noise = (qpss[-1] - qpss[0]) / 2.0 / qps if qps else 0.0
    # batched-vs-sequential bit-identity probes (the r8 bucketing contract
    # carried into serving; conv topologies reassociate at ulp across batch
    # shapes on XLA:CPU — the documented docs/COMPILE_CACHE.md exception —
    # so the conv probe compares the same bucket shape, the decode probe is
    # end-to-end exact):
    # 1. classify: scheduler result == direct forward at the same bucket
    pad = np.concatenate([images[:3], np.zeros((1, 28, 28, 1), np.float32)])
    direct = np.asarray(lenet.output(pad))[:3]
    via = router.submit("lenet", images[:3], lane="interactive"
                        ).result(timeout=60)
    # 2. decode: coalesced 2-prompt batch == each prompt generated alone
    both, _ = gen.execute([np.asarray(p, np.int32) for p in prompts[:2]],
                          max_new_tokens=max_new_tokens)
    solo = [gen.execute([np.asarray(p, np.int32)],
                        max_new_tokens=max_new_tokens)[0][0]
            for p in prompts[:2]]
    bit_identical = bool(np.array_equal(np.asarray(via), direct)) \
        and list(both) == list(solo)
    router.shutdown()
    model_desc = (f"LeNet classify x{classify_requests} (interactive lane) "
                  f"+ Bert.tiny causal-mlm KV-decode x{generate_requests} "
                  f"({max_new_tokens} new tokens, batch lane), per-model "
                  "schedulers, scheduler-level round trip")
    return [{
        "metric": "serving_p99_latency_ms",
        "model": model_desc,
        "value": round(p99, 2),
        "noise": f"±{round(100 * p99_noise, 1)}% (3-sample spread/2)",
        "unit": "ms (submit -> complete, p99 over all requests)",
        "steady_recompiles": int(steady_recompiles),  # must be 0
        "batched_bit_identical": bit_identical,       # must be True
        "vs_baseline": None,  # first number on this axis
    }, {
        "metric": "serving_qps",
        "model": model_desc,
        "value": round(qps, 2),
        "noise": f"±{round(100 * qps_noise, 1)}% (3-sample spread/2)",
        "unit": "completed requests/sec (mixed workload, to drain)",
        "vs_baseline": None,  # first number on this axis
    }]


def bench_request_tracing_overhead(classify_requests: int = 144,
                                   generate_requests: int = 6,
                                   max_new_tokens: int = 8):
    """request_tracing_overhead: the r13 mixed two-model serving workload's
    wall time with request tracing FULLY ON (DL4J_TPU_TRACE_SAMPLE=1 —
    every request emits queue/fill/compute phase spans, batch pad/device
    spans, per-token decode spans, and a flight-recorder record) over the
    identical workload with tracing OFF (=0 — timestamps still stamped,
    nothing emitted). Sampling at 100% is the WORST case; the default 2%
    head sample costs a fraction of this. Target ≤ 1.05x, the r9
    telemetry_overhead convention (docs/OBSERVABILITY.md). Median-of-3 of
    the ratio with the standard noise field."""
    from deeplearning4j_tpu.data.bucketing import BucketingPolicy
    from deeplearning4j_tpu.serving import ModelRouter, ServingModel
    from deeplearning4j_tpu.zoo.bert import Bert

    lenet = _build_lenet()
    clf = ServingModel(lenet, "lenet-tr", bucketing=BucketingPolicy(
        batch_buckets=(1, 2, 4, 8)))
    bert = Bert.tiny(causal=True, task="mlm", vocab_size=64, max_length=32,
                     hidden_dropout=0.0).init()
    gen = ServingModel(bert, "bert-tr-decode", kind="generate",
                       bucketing=BucketingPolicy(batch_buckets=(1, 2, 4),
                                                 seq_buckets=(8,)))
    router = ModelRouter(name="tracing-bench")
    router.register(clf, max_wait_ms=1.0, queue_limit=256)
    router.register(gen, max_wait_ms=1.0, queue_limit=256)
    router.warmup()

    rng = np.random.default_rng(0)
    images = rng.normal(size=(8, 28, 28, 1)).astype(np.float32)
    prompts = [list(rng.integers(1, 64, size=5)) for _ in range(4)]

    def one_run() -> float:
        t0 = time.perf_counter()
        futs = []
        for i in range(generate_requests):
            futs.append(router.submit(
                "bert-tr-decode",
                np.asarray(prompts[i % len(prompts)], np.int32),
                lane="batch", max_new_tokens=max_new_tokens))
        for i in range(classify_requests):
            futs.append(router.submit("lenet-tr", images[i % 8][None],
                                      lane="interactive"))
        for f in futs:
            f.result(timeout=300)
        return time.perf_counter() - t0

    saved = os.environ.get("DL4J_TPU_TRACE_SAMPLE")

    def timed(sample: str) -> float:
        os.environ["DL4J_TPU_TRACE_SAMPLE"] = sample
        one_run()  # settle at this sampling mode
        return one_run()

    try:
        # counterbalanced A/B: alternate which mode is timed first — a
        # sequential off-then-on pair reads monotone machine drift as
        # tracing overhead (measured: the same workload A/B'd per-mode
        # back-to-back shows ≈0 cost, while off→on ordering showed a
        # phantom ~5%)
        order = itertools.cycle([("0", "1"), ("1", "0")])

        def one_ratio():
            first, second = next(order)
            t = {first: timed(first), second: timed(second)}
            return t["1"] / t["0"]

        ratio, noise = _med3(one_ratio)
    finally:
        if saved is None:
            os.environ.pop("DL4J_TPU_TRACE_SAMPLE", None)
        else:
            os.environ["DL4J_TPU_TRACE_SAMPLE"] = saved
        router.shutdown()
    return {
        "metric": "request_tracing_overhead",
        "model": (f"LeNet classify x{classify_requests} + Bert.tiny "
                  f"KV-decode x{generate_requests} ({max_new_tokens} new "
                  "tokens), scheduler round trip, DL4J_TPU_TRACE_SAMPLE=1 "
                  "(every request traced) vs 0"),
        "value": round(ratio, 4),
        "noise": noise,
        "unit": "x untraced serving wall time (1.0 = free)",
        # ≤ 1.0 means the ≤ 1.05x overhead target is met
        "vs_baseline": round(ratio / 1.05, 4),
    }


def bench_serving_resilience(classify_requests: int = 96,
                             generate_requests: int = 4,
                             max_new_tokens: int = 6,
                             storm_reloads: int = 3):
    """serving_resilience_overhead + serving_reload_p99_delta_ms (ISSUE 13,
    docs/SERVING.md#resilience).

    Overhead: the r13 mixed two-model workload on a router with the full
    resilience layer armed (supervised watchdog wrapping the worker loop,
    per-model circuit breaker gating every submit and recording every batch
    outcome) over an identical router with both OFF (``breaker=None,
    supervised=False``). Target ≤ 1.05x, the r9 telemetry_overhead
    convention. Counterbalanced A/B (which router is timed first alternates
    per median sample — the r17 lesson: sequential ordering reads monotone
    machine drift as phantom overhead), median-of-3 of the ratio.

    Reload delta: p99 submit→complete latency of the same traffic WHILE a
    rolling-reload storm runs (``storm_reloads`` back-to-back
    ``ModelRouter.reload`` calls — restore + shadow warmup + canary + swap
    on the caller's thread) minus p99 over a steady window of the same
    duration. The contract is zero shed and zero steady-state recompiles
    (both carried in the record); the delta is what the storm's CPU theft
    (shadow warmup compiles XLA programs) costs the p99 tail. Floored at
    0.5 ms: a storm measurably CHEAPER than steady state is timer noise,
    and the floor keeps the LOWER_BETTER gate band multiplicative. On this
    CPU container the shadow compiles contend for the same cores that
    serve — on a real TPU host the compile is host-side while serving is
    device-side, so this number is an upper bound (the r6 convention: CPU
    proves the contract, cannot rank the cost)."""
    import threading

    from deeplearning4j_tpu.data.bucketing import BucketingPolicy
    from deeplearning4j_tpu.serving import ModelRouter, ServingModel
    from deeplearning4j_tpu.util import telemetry as tm
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer
    from deeplearning4j_tpu.zoo.bert import Bert

    def build_router(tag: str, **sched_kw):
        lenet = _build_lenet()
        clf = ServingModel(lenet, f"lenet-{tag}",
                           bucketing=BucketingPolicy(
                               batch_buckets=(1, 2, 4, 8)))
        bert = Bert.tiny(causal=True, task="mlm", vocab_size=64,
                         max_length=32, hidden_dropout=0.0).init()
        gen = ServingModel(bert, f"bert-{tag}-decode", kind="generate",
                           bucketing=BucketingPolicy(batch_buckets=(1, 2, 4),
                                                     seq_buckets=(8,)))
        router = ModelRouter(name=f"resilience-bench-{tag}")
        router.register(clf, max_wait_ms=1.0, queue_limit=512, **sched_kw)
        router.register(gen, max_wait_ms=1.0, queue_limit=512, **sched_kw)
        router.warmup()
        return router

    # the A/B pair: the full layer armed vs both legs off
    on_router = build_router("rs")
    off_router = build_router("rs0", breaker=None, supervised=False)

    rng = np.random.default_rng(0)
    images = rng.normal(size=(8, 28, 28, 1)).astype(np.float32)
    prompts = [list(rng.integers(1, 64, size=5)) for _ in range(4)]

    def one_run(router, tag: str) -> float:
        t0 = time.perf_counter()
        futs = []
        for i in range(generate_requests):
            futs.append(router.submit(
                f"bert-{tag}-decode",
                np.asarray(prompts[i % len(prompts)], np.int32),
                lane="batch", max_new_tokens=max_new_tokens))
        for i in range(classify_requests):
            futs.append(router.submit(f"lenet-{tag}", images[i % 8][None],
                                      lane="interactive"))
        for f in futs:
            f.result(timeout=300)
        return time.perf_counter() - t0

    def timed(which: str) -> float:
        router, tag = ((on_router, "rs") if which == "on"
                       else (off_router, "rs0"))
        one_run(router, tag)  # settle
        return one_run(router, tag)

    order = itertools.cycle([("on", "off"), ("off", "on")])

    def one_ratio():
        first, second = next(order)
        t = {first: timed(first), second: timed(second)}
        return t["on"] / t["off"]

    ratio, ratio_noise = _med3(one_ratio)

    # -------- reload storm p99 delta (on_router; the off one is done)
    off_router.shutdown()
    tmpdir = tempfile.mkdtemp(prefix="bench-reload-")
    try:
        paths = []
        for i in range(storm_reloads):
            p = os.path.join(tmpdir, f"v{i}.zip")
            ModelSerializer.write_model(_build_lenet(seed=i + 1), p,
                                        save_updater=False)
            paths.append(p)

        def traffic(stop, lat, errs):
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    on_router.submit("lenet-rs",
                                     images[0][None],
                                     lane="interactive").result(timeout=120)
                    lat.append(time.perf_counter() - t0)
                except Exception as e:  # noqa: BLE001 — zero-shed contract
                    errs.append(repr(e))

        def p99_window(storm: bool, duration: float):
            stop, lat, errs = threading.Event(), [], []
            threads = [threading.Thread(target=traffic,
                                        args=(stop, lat, errs))
                       for _ in range(3)]
            for t in threads:
                t.start()
            time.sleep(0.1)
            t0 = time.perf_counter()
            if storm:
                for p in paths:
                    on_router.reload("lenet-rs", p)
            else:
                time.sleep(duration)
            wall = time.perf_counter() - t0
            time.sleep(0.1)
            stop.set()
            for t in threads:
                t.join(timeout=60)
            if not lat:
                # every request in the window failed: surface the REAL
                # diagnosis (the zero-shed contract broke) instead of an
                # IndexError from indexing an empty quantile list
                raise RuntimeError(
                    f"reload-delta window completed 0 requests "
                    f"({len(errs)} errors; first: {errs[:1]})")
            lat.sort()
            p99 = lat[min(len(lat) - 1,
                          int(round(0.99 * (len(lat) - 1))))] * 1e3
            return p99, wall, len(errs), len(lat)

        tele = tm.get_telemetry()
        rec = lambda: tele.counter_total(  # noqa: E731
            "serving.recompiles_total", model="lenet-rs")
        storm_wall = None
        shed = 0
        n_requests = 0
        rec0 = rec()

        def one_delta():
            nonlocal storm_wall, shed, n_requests
            # storm first so the steady window can duration-match it; the
            # traffic loop itself is identical on both sides
            p99_storm, storm_wall, e1, n1 = p99_window(True, 0.0)
            p99_steady, _w, e2, n2 = p99_window(False, storm_wall)
            shed += e1 + e2
            n_requests += n1 + n2
            return p99_storm - p99_steady

        vals = sorted(one_delta() for _ in range(3))
        delta = vals[1]
        # noise over the FLOORED value: a near-zero delta's spread divided
        # by itself would explode (or flip sign), and the floor is what the
        # gate band is built on
        delta_noise = (f"±{round(100 * (vals[-1] - vals[0]) / 2.0 / max(delta, 0.5), 1)}"
                       "% (3-sample spread/2 over the floored value)")
        steady_recompiles = rec() - rec0
        reload_version = on_router.get("lenet-rs")[0].version
    finally:
        on_router.shutdown()
        shutil.rmtree(tmpdir, ignore_errors=True)

    model_desc = (f"LeNet classify x{classify_requests} (interactive) + "
                  f"Bert.tiny KV-decode x{generate_requests} "
                  f"({max_new_tokens} new tokens, batch lane), per-model "
                  "schedulers")
    return [{
        "metric": "serving_resilience_overhead",
        "model": (model_desc + "; supervised watchdog + circuit breaker ON "
                  "vs breaker=None, supervised=False (counterbalanced A/B)"),
        "value": round(ratio, 4),
        "noise": ratio_noise,
        "unit": "x unguarded serving wall time (1.0 = free)",
        # ≤ 1.0 means the ≤ 1.05x overhead target is met
        "vs_baseline": round(ratio / 1.05, 4),
    }, {
        "metric": "serving_reload_p99_delta_ms",
        "model": (f"LeNet classify closed-loop x3 threads; p99 during a "
                  f"{storm_reloads}-reload rolling storm (restore + shadow "
                  "warmup + canary + swap) minus duration-matched steady "
                  "p99; floored at 0.5 ms; CPU container — shadow compiles "
                  "contend with serving cores, an upper bound vs a real "
                  "TPU host"),
        "value": round(max(delta, 0.5), 2),
        "raw_delta_ms": round(delta, 2),
        "noise": delta_noise,
        "unit": "ms added to p99 by a reload storm (0.5 = floor)",
        "storm_reloads": storm_reloads * 3,       # 3 samples x storm
        "storm_shed": shed,                       # must be 0
        "storm_requests": n_requests,
        "steady_recompiles": int(steady_recompiles),  # must be 0
        "reload_version": int(reload_version),
        "vs_baseline": None,  # first number on this axis
    }]


def bench_decode_paged(streams: int = 32, prompt_len: int = 16,
                       max_new: int = 8):
    """concurrent_streams_per_device (ISSUE 15 headline, HIGHER_BETTER):
    how many decode streams ONE device's KV bytes hold under the paged
    block pool vs the r13 contiguous layout. Deterministic byte accounting
    of the placement (the r10/r19 convention — a regression means the
    pool stopped paging, not that a timer wobbled): the pool is sized to
    the contiguous ceiling's exact byte budget (64 blocks × 16 slots =
    1024 token slots = 8 streams × max_length 128), then a REAL mixed
    batch of 32 typical-length streams (prompt 16 + 8 new = 24 tokens →
    2 blocks each) is admitted and decoded through it — 4× the streams in
    the same bytes, measured from the pool's high-water mark, not
    computed."""
    from deeplearning4j_tpu.serving.generate import Generator
    from deeplearning4j_tpu.zoo.bert import Bert

    net = Bert.tiny(causal=True, task="mlm", vocab_size=64, max_length=128,
                    hidden_dropout=0.0).init()
    gen = Generator(net, paged=True, block_size=16, pool_blocks=64,
                    batch_buckets=(1, 2, 4, 8, 16, 32),
                    prefill_buckets=(16,))
    pool = gen.pool
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, 64, size=prompt_len)))
               for _ in range(streams)]
    out = gen.generate(prompts, max_new_tokens=max_new)
    assert all(len(r) == max_new for r in out)
    assert pool.free_blocks() == pool.num_blocks  # all freed
    ceiling = pool.contiguous_stream_ceiling()
    peak = pool.peak_streams
    return {
        "metric": "concurrent_streams_per_device",
        "model": (f"BERT-tiny causal decoder, paged KV pool "
                  f"{pool.num_blocks}x{pool.block_size} slots = "
                  f"{pool.pool_bytes()} B (the contiguous layout's exact "
                  f"budget for {ceiling} streams @ max_length "
                  f"{gen.max_length}); {streams} real streams of "
                  f"{prompt_len}+{max_new} tokens admitted and decoded — "
                  f"deterministic byte accounting of the placement, "
                  f"measured at the pool high-water mark"),
        "value": int(peak),
        "noise": "±0.0% (deterministic block accounting)",
        "unit": "streams/device",
        "vs_baseline": round(peak / ceiling, 4),  # vs contiguous ceiling
    }


def bench_speculative_decode(batch: int = 4, prompt_len: int = 8,
                             max_new: int = 24):
    """speculative_decode_tokens_per_sec vs the non-speculative paged
    baseline (honest CPU A/B per the r6/r15 convention): greedy decode of
    the same prompts through (a) the plain per-token paged loop and
    (b) the speculative path with a random-init Bert.draft — on CPU the
    draft accepts ~nothing, so every round pays draft steps + a verify
    window to emit ~1 token and speculation LOSES; the committed value
    pins today's spec-path throughput so the machinery can't silently
    regress, while the note carries the perfect-draft ceiling (the
    window-amortization upper bound a distilled draft approaches). CPU
    cannot rank the win — acceptance rates on real traffic ride the
    per-request ``draft_accept_rate`` ruler (docs/OBSERVABILITY.md)."""
    from deeplearning4j_tpu.serving.generate import Generator
    from deeplearning4j_tpu.zoo.bert import Bert

    net = Bert.tiny(causal=True, task="mlm", vocab_size=64, max_length=64,
                    hidden_dropout=0.0).init()
    draft = Bert.draft(vocab_size=64, max_length=64).init()
    buckets = dict(batch_buckets=(1, 2, 4), prefill_buckets=(8,))
    g_plain = Generator(net, paged=True, block_size=16, **buckets)
    g_spec = Generator(net, paged=True, block_size=16, draft_net=draft,
                       spec_tokens=4, **buckets)
    g_self = Generator(net, paged=True, block_size=16, draft_net=net,
                       spec_tokens=4, **buckets)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, 64, size=prompt_len)))
               for _ in range(batch)]
    for g in (g_plain, g_spec, g_self):
        g.warmup()
        g.generate(prompts, max_new_tokens=max_new)  # warm the whole loop
    want = g_plain.generate(prompts, max_new_tokens=max_new)
    assert g_spec.generate(prompts, max_new_tokens=max_new) == want
    assert g_self.generate(prompts, max_new_tokens=max_new) == want

    def tps(g, stats=None):
        def run():
            t0 = time.perf_counter()
            out = g.generate(prompts, max_new_tokens=max_new, stats=stats)
            dt = time.perf_counter() - t0
            return sum(len(r) for r in out) / dt
        return _med3(run)

    base, base_noise = tps(g_plain)
    st = {}
    spec, spec_noise = tps(g_spec, stats=st)
    ceiling, _ = tps(g_self)
    return {
        "metric": "speculative_decode_tokens_per_sec",
        "model": (f"BERT-tiny target + Bert.draft (1L/64H random-init, "
                  f"accept {st.get('spec_accept_rate', 0):.3f}) greedy "
                  f"B={batch} T+{max_new}; honest CPU A/B: plain paged "
                  f"{base:.1f} tok/s {base_noise}, speculative "
                  f"{spec:.1f} tok/s, perfect-draft ceiling "
                  f"{ceiling:.1f} tok/s (window amortization at accept "
                  f"1.0) — CPU cannot rank the win, a distilled draft + "
                  f"chip verify economics decide it; token identity "
                  f"asserted in-run"),
        "value": round(spec, 2),
        "noise": spec_noise,
        "unit": "tokens/sec",
        "vs_baseline": round(spec / base, 4),  # vs non-speculative
    }


def bench_prefix_decode(streams: int = 64, system_len: int = 56,
                        suffix_len: int = 4, max_new: int = 4):
    """concurrent_streams_per_device on PREFIX-HEAVY traffic (ISSUE 16
    headline, HIGHER_BETTER) plus prefix_cache_ttft_speedup. Deterministic
    byte accounting in the SAME usable byte budget as the r11 record
    (1024 token slots = the contiguous layout's 8 streams @ max_length
    128; here 128 blocks × 8 slots): with a 56-token system prompt
    resident ONCE in the radix cache (7 shared blocks), 64 streams of
    64-token context (56 shared + 4 unique suffix + 4 generated) each
    admit ONE fresh block — 7 + 64 = 71 of 128 blocks — where the
    unshared paged layout would need 8 blocks/stream (512 total) and the
    r11 mixed batch held 32 streams of 24-token context. Identity vs an
    uncached paged reference is asserted in-run. The TTFT companion is a
    wall-clock A/B on this host: first-token latency for a warm-cache
    batch (prefill resumes at position 56, an 8-wide window) vs the same
    batch cold (full 64-wide prefill), median of 3."""
    from deeplearning4j_tpu.serving.generate import Generator
    from deeplearning4j_tpu.zoo.bert import Bert

    net = Bert.tiny(causal=True, task="mlm", vocab_size=64, max_length=128,
                    hidden_dropout=0.0).init()
    buckets = dict(batch_buckets=(1, 8, streams), prefill_buckets=(8, 64))
    gen = Generator(net, paged=True, block_size=8, pool_blocks=128,
                    prefix_cache=True, **buckets)
    ref = Generator(net, paged=True, block_size=8, pool_blocks=600,
                    **buckets)
    pool = gen.pool
    rng = np.random.default_rng(0)
    system = list(map(int, rng.integers(1, 64, size=system_len)))
    prompts = [system + list(map(int, rng.integers(1, 64, size=suffix_len)))
               for _ in range(streams)]
    # resident system prompt: one prior request commits the shared blocks
    gen.generate([prompts[0]], max_new_tokens=max_new)
    out = gen.generate(prompts, max_new_tokens=max_new)
    assert out == ref.generate(prompts, max_new_tokens=max_new)
    ok, detail = pool.conservation()
    assert ok, detail
    ceiling = pool.contiguous_stream_ceiling()
    peak = pool.peak_streams
    shared_blocks = system_len // pool.block_size
    headline = {
        "metric": "concurrent_streams_per_device",
        "model": (f"BERT-tiny causal decoder, prefix-heavy traffic: paged "
                  f"KV pool {pool.num_blocks}x{pool.block_size} slots = "
                  f"{pool.pool_bytes()} B (the r11 budget: contiguous "
                  f"ceiling {ceiling} streams @ max_length "
                  f"{gen.max_length}); {streams} streams of "
                  f"{system_len}+{suffix_len}+{max_new}-token context "
                  f"sharing the {system_len}-token system prompt via the "
                  f"radix cache ({shared_blocks} resident blocks, 1 fresh "
                  f"block/stream) — deterministic block accounting at the "
                  f"pool high-water mark, token identity vs the uncached "
                  f"paged reference asserted in-run"),
        "value": int(peak),
        "noise": "±0.0% (deterministic block accounting)",
        "unit": "streams/device",
        "vs_baseline": round(peak / ceiling, 4),  # vs contiguous ceiling
    }

    # --- TTFT A/B: warm radix cache vs cold, same batch, max_new=1
    ttft_prompts = prompts[:8]
    gen.warmup()

    def cold_once():
        gen.cache.flush()
        t0 = time.perf_counter()
        gen.generate(ttft_prompts, max_new_tokens=1)
        return time.perf_counter() - t0

    cold_once()  # trace anything warmup missed before timing
    cold_s, cold_noise = _med3(cold_once)
    gen.generate(ttft_prompts, max_new_tokens=1)  # prime the trie

    def warm_once():
        t0 = time.perf_counter()
        gen.generate(ttft_prompts, max_new_tokens=1)
        return time.perf_counter() - t0

    warm_s, warm_noise = _med3(warm_once)
    ttft = {
        "metric": "prefix_cache_ttft_speedup",
        "model": (f"same decoder/pool: first-token latency for a warm "
                  f"{len(ttft_prompts)}-stream batch (prefill resumes at "
                  f"position {system_len}, 8-wide window, "
                  f"{warm_s * 1e3:.1f} ms {warm_noise}) vs cold "
                  f"({cold_s * 1e3:.1f} ms {cold_noise}, full 64-wide "
                  f"prefill), this host"),
        "value": round(cold_s / warm_s, 4),
        "noise": warm_noise,
        "unit": "x",
        "vs_baseline": round(cold_s / warm_s, 4),  # vs cold prefill
    }
    return [headline, ttft]


def bench_fleet(n_big: int = 4, window_s: float = 4.0, clients: int = 12):
    """fleet_qps_scaling_efficiency (ISSUE 18 headline, HIGHER_BETTER,
    gated) + fleet_routing_overhead_ms (LOWER_BETTER). A FleetRouter over
    real worker processes serving a compute-weighted dense classifier
    (128->1024->1024->8; each worker pinned single-threaded via
    XLA_FLAGS=--xla_cpu_multi_thread_eigen=false + OMP_NUM_THREADS=1 so
    worker count, not intra-op threading, is the parallelism axis).

    Efficiency = QPS(N=4) / (min(N, host_cores) x QPS(N=1)) — normalized
    by EFFECTIVE parallelism, the honest-CPU rule: on this 1-core
    container 4 single-threaded workers cannot exceed one core's
    throughput, so the raw N x QPS(1) denominator would measure the host,
    not the fleet (the dp_sharding_efficiency precedent,
    HOST_CONDITION_FLOOR in regression_gate.py). At saturation the metric
    becomes the disaggregation tax: what routing + 4-way process
    multiplexing retain of one worker's direct throughput. On a >=5-core
    host the SAME expression measures true QPS scaling.

    The overhead companion is p50(serial request through a 1-worker
    fleet) - p50(same request direct to that worker): the per-hop cost of
    the routing tier (rendezvous hash + header relay + pooled proxy
    connection), in ms."""
    import http.client
    import threading

    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.serving.fleet import FleetRouter, fleet_spec
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer

    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
            .batch_buckets((1, 2, 4, 8)).list()
            .layer(DenseLayer(n_in=128, n_out=1024, activation="relu"))
            .layer(DenseLayer(n_in=1024, n_out=1024, activation="relu"))
            .layer(OutputLayer(n_in=1024, n_out=8, loss="mcxent",
                               activation="softmax"))
            .set_input_type(InputType.feed_forward(128)).build())
    net = MultiLayerNetwork(conf).init()
    tmp = tempfile.mkdtemp(prefix="dl4j_fleet_bench_")
    clf_path = os.path.join(tmp, "clf.zip")
    ModelSerializer.write_model(net, clf_path, save_updater=False)
    spec = fleet_spec(
        models=[{"id": "clf", "path": clf_path, "kind": "classify",
                 "register": {"max_wait_ms": 2.0, "queue_limit": 512}}],
        env={"JAX_PLATFORMS": "cpu", "OMP_NUM_THREADS": "1",
             "XLA_FLAGS": "--xla_cpu_multi_thread_eigen=false"})
    row = np.random.default_rng(0).normal(size=(1, 128)).tolist()
    payload = json.dumps({"inputs": row}).encode()

    def post_one(conn):
        conn.request("POST", "/v1/models/clf/infer", body=payload,
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        r.read()
        return r.status

    def qps_window(fleet):
        done = [0] * clients
        t_end = time.perf_counter() + window_s

        def client(i):
            conn = http.client.HTTPConnection("127.0.0.1", fleet.port,
                                              timeout=60)
            try:
                while time.perf_counter() < t_end:
                    if post_one(conn) == 200:
                        done[i] += 1
            finally:
                conn.close()

        t0 = time.perf_counter()
        ts = [threading.Thread(target=client, args=(i,))
              for i in range(clients)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return sum(done) / (time.perf_counter() - t0)

    def p50_serial(port, n=80):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            lats = []
            for _ in range(n):
                t0 = time.perf_counter()
                post_one(conn)
                lats.append(time.perf_counter() - t0)
        finally:
            conn.close()
        return sorted(lats)[n // 2]

    def boot(n):
        f = FleetRouter(spec, n_workers=n, health_interval_s=0.25,
                        name=f"bench{n}").start()
        p50_serial(f.port, n=16)  # settle conn pools + anything unwarmed
        return f

    f1 = boot(1)
    q1, q1_noise = _med3(lambda: qps_window(f1))
    w_port = f1.workers[0].port
    direct_p50, _dn = _med3(lambda: p50_serial(w_port))
    fleet_p50, fleet_noise = _med3(lambda: p50_serial(f1.port))
    f1.stop()
    f4 = boot(n_big)
    q4, q4_noise = _med3(lambda: qps_window(f4))
    f4.stop()

    cores = os.cpu_count() or 1
    denom = min(n_big, cores)
    eff = q4 / (denom * q1)
    overhead_ms = max(0.0, (fleet_p50 - direct_p50) * 1e3)
    scaling = {
        "metric": "fleet_qps_scaling_efficiency",
        "model": (f"FleetRouter over {n_big} worker processes vs 1, dense "
                  f"128->1024->1024->8 classifier, {clients} persistent "
                  f"HTTP clients x {window_s:.0f}s windows; workers pinned "
                  f"single-threaded (eigen+OMP=1) so worker count is the "
                  f"only parallelism axis. QPS(N={n_big})={q4:.1f} "
                  f"{q4_noise}, QPS(N=1)={q1:.1f} {q1_noise}; efficiency "
                  f"normalized by EFFECTIVE parallelism min(N, host_cores"
                  f"={cores})={denom} — on this 1-core host the metric is "
                  f"the disaggregation tax at core saturation (honest-CPU "
                  f"rule, the dp_sharding precedent); on >=5 cores the "
                  f"same expression is true QPS scaling"),
        "value": round(eff, 4),
        "noise": q4_noise,
        "unit": "fraction",
        "vs_baseline": round(eff, 4),  # vs perfect scaling at 1.0
    }
    routing = {
        "metric": "fleet_routing_overhead_ms",
        "model": (f"p50 of a serial classify request through a 1-worker "
                  f"fleet ({fleet_p50 * 1e3:.2f} ms) minus p50 direct to "
                  f"the worker ({direct_p50 * 1e3:.2f} ms): rendezvous "
                  f"hash + header relay + pooled proxy hop, this host"),
        "value": round(overhead_ms, 3),
        "noise": fleet_noise,
        "unit": "ms",
        "vs_baseline": round(overhead_ms, 3),
    }
    return [scaling, routing]


def main():
    import jax

    from deeplearning4j_tpu.util.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    extra = []
    if dev.platform == "tpu":
        # Device-sized phases, at the r05 sizes. No try/except: one that
        # raises ends the run non-zero before anything is printed.
        steps = 20
        result = bench_resnet50(batch=256, image=224, steps=steps)
        # batch 128: r2 sweep — 32 underutilizes the MXU (877 samples/s vs
        # 1,166 at 128)
        extra.append(bench_bert(batch=128, seq=128, steps=steps))
        extra.append(bench_attention_2k())
        extra.append(bench_lstm_char_rnn(batch=128, seq=128, hidden=512,
                                         steps=60))
    else:
        print(f"no TPU (platform {dev.platform!r}): device-sized phases "
              "not run", file=sys.stderr)
        result = {"metric": None, "value": None,
                  "note": "no TPU: device-sized phases not run"}
    result["device"] = device
    try:
        extra.append(bench_scaling())
    except Exception as e:
        print(f"scaling bench failed: {type(e).__name__}: {e}", file=sys.stderr)
    try:
        extra.append(bench_zero_memory())
    except Exception as e:
        print(f"zero memory bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        extra.append(bench_tp_bert_smoke())
    except Exception as e:
        print(f"tp bert smoke failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        extra.append(bench_compression_ratio())
    except Exception as e:
        print(f"compression ratio bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        extra.extend(bench_pipeline())
    except Exception as e:
        print(f"pipeline bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        extra.append(bench_host_pipeline(batch=16, n_batches=24))
    except Exception as e:
        print(f"host pipeline bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        extra.append(bench_recompile_overhead())
    except Exception as e:
        print(f"recompile overhead bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        # B=64 even on CPU: smaller batches make the step so short that
        # scheduler noise swamps the ~µs-scale span cost being measured
        extra.append(bench_telemetry_overhead(batch=64))
    except Exception as e:
        print(f"telemetry overhead bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        extra.append(bench_cost_attribution(batch=64))
    except Exception as e:
        print(f"cost attribution bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        extra.append(bench_optimizer_update_share(batch=64))
    except Exception as e:
        print(f"optimizer update share bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        extra.append(bench_autotune_dispatch())
    except Exception as e:
        print(f"autotune dispatch bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        # B=64 like the other overhead benches: the per-step costs being
        # measured (heartbeat thread wakeups, async-checkpoint enqueue) are
        # fixed, so tiny steps would drown them in scheduler noise
        extra.append(bench_elastic_overhead(batch=64))
    except Exception as e:
        print(f"elastic overhead bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        extra.extend(bench_serving())
    except Exception as e:
        print(f"serving bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        extra.append(bench_request_tracing_overhead())
    except Exception as e:
        print(f"request tracing overhead bench failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    try:
        extra.extend(bench_serving_resilience())
    except Exception as e:
        print(f"serving resilience bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        extra.append(bench_decode_paged())
    except Exception as e:
        print(f"paged decode bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        extra.append(bench_speculative_decode())
    except Exception as e:
        print(f"speculative decode bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        # ISSUE 16: prefix-heavy streams-per-device (supersedes the r11
        # mixed-batch measurement of the same metric) + TTFT speedup
        extra.extend(bench_prefix_decode())
    except Exception as e:
        print(f"prefix decode bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        # ISSUE 18: disaggregated fleet — QPS scaling efficiency over N
        # real worker processes (normalized by effective host parallelism,
        # see bench_fleet) + the routing tier's per-hop p50 overhead
        extra.extend(bench_fleet())
    except Exception as e:
        print(f"fleet bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    result["extra_metrics"] = extra
    print(json.dumps(result))


if __name__ == "__main__":
    main()
