"""A second trainable model comes as files only: a configuration, its plain
reference, its builder and one manifest entry, written into a copy of the
benchmark by this test. ``staged_ring``, ``train.py``, ``work.py`` and the
metric readers are not edited and know no model by name."""

import json
import os

import pytest

from chipbench import manifest, run

import chipbench_tiny as tiny

REFERENCE = '''
"""Plain float32 two-layer perceptron with Adam (test only)."""
import jax
import jax.numpy as jnp

ADAM = {"lr": 1e-2, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}


def make_weights(seed, cfg):
    k = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 4)
    d, h, c = cfg["n_in"], cfg["n_hidden"], cfg["n_out"]
    return {"w1": jax.random.normal(k[0], (d, h)) / d ** 0.5,
            "b1": 0.1 * jax.random.normal(k[1], (h,)),
            "w2": jax.random.normal(k[2], (h, c)) / h ** 0.5,
            "b2": 0.1 * jax.random.normal(k[3], (c,))}


def make_batches(seed, cfg, n, batch):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31) + 1))
    x = jax.random.normal(kx, (n, batch, cfg["n_in"]))
    y = jax.nn.one_hot(jax.random.randint(ky, (n, batch), 0, cfg["n_out"]),
                       cfg["n_out"])
    return [(x[i], y[i]) for i in range(n)]


def train_flops_per_example(cfg):
    return 3 * 2 * (cfg["n_in"] * cfg["n_hidden"]
                    + cfg["n_hidden"] * cfg["n_out"])


def _loss(w, x, y):
    h = jnp.maximum(x @ w["w1"] + w["b1"], 0.0)
    lg = h @ w["w2"] + w["b2"]
    return -jnp.mean(jnp.sum(y * jax.nn.log_softmax(lg), axis=-1))


def follow(w0, batches, keep=(), lower=None, fault=None):
    tm = jax.tree_util.tree_map
    w, m, v = w0, tm(jnp.zeros_like, w0), tm(jnp.zeros_like, w0)
    out = {"losses": []}
    for t, (x, y) in enumerate(batches, start=1):
        loss, g = jax.value_and_grad(_loss)(w, x, y)
        m = tm(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = tm(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        w = tm(lambda p, a, b: p - ADAM["lr"] * (a / (1 - 0.9 ** t)) / (
            jnp.sqrt(b / (1 - 0.999 ** t)) + ADAM["eps"]), w, m, v)
        out["losses"].append(float(loss))
        if t == 1:
            out["grad_norms"] = {k: float(jnp.linalg.norm(a))
                                 for k, a in g.items()}
            out["first_grads"] = {k: g[k] for k in keep}
    out["change_norms"] = {k: float(jnp.linalg.norm(w[k] - w0[k]))
                           for k in w0}
    out["sizes"] = {k: int(a.size) for k, a in w0.items()}
    return out
'''

BUILDER = '''
"""The program's MultiLayerNetwork as that perceptron (test only)."""
import jax.numpy as jnp

NAMES = {(0, "W"): "w1", (0, "b"): "b1", (1, "W"): "w2", (1, "b"): "b2"}


def build(cfg):
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_in=cfg["n_in"], n_out=cfg["n_hidden"],
                              activation="relu"))
            .layer(OutputLayer(n_in=cfg["n_hidden"], n_out=cfg["n_out"],
                               loss="mcxent", activation="softmax"))
            .set_input_type(InputType.feed_forward(cfg["n_in"])).build())
    return MultiLayerNetwork(conf).init()


def load(net, weights):
    for (i, leaf), name in NAMES.items():
        net.params[i][leaf] = jnp.array(weights[name], copy=True)


def export(layers):
    return {name: layers[i][leaf] for (i, leaf), name in NAMES.items()}


def adam_m(net):
    return export([s["m"] for s in net.opt_states])
'''

CONFIG = {"name": "mlp-tiny", "builder": "tiny.mlp", "reference": "tiny_mlp",
          "n_in": 12, "n_hidden": 32, "n_out": 5, "per_chip_batch": 16,
          "control": "none",
          "limits": {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
                     "big_leaf_size": 100, "big_grad_norm_gap": 1e-3,
                     "grad_angle": {"w1": 1e-5, "w2": 1e-5},
                     "change_norm_gap": 0.01}}


@pytest.fixture
def tree(tmp_path, monkeypatch):
    tiny.quiet_cache(monkeypatch)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / ".out"))
    man = tiny.tiny_tree(tmp_path, monkeypatch)
    before = {}
    for dirpath, _d, files in os.walk(manifest.HERE):
        for f in files:
            with open(os.path.join(dirpath, f), "rb") as fh:
                before[os.path.join(dirpath, f)] = fh.read()
    for sub, name, text in (("reference", "tiny_mlp.py", REFERENCE),
                            ("builders", "tiny.mlp.py", BUILDER),
                            ("configs", "mlp-tiny.json", json.dumps(CONFIG))):
        with open(os.path.join(manifest.HERE, sub, name), "w") as f:
            f.write(text)
    man["configs"].append({"name": "mlp-tiny", "source": "test",
                           "file": "chipbench/configs/mlp-tiny.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "tiny-mlp-staged", "config": "mlp-tiny",
                             "traffic": "tiny-staged", "chips": 1,
                             "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if "tiny-fit-staged" in m.get("workloads", []):
                m["workloads"].append("tiny-mlp-staged")
    return man, before


def test_a_second_model_trains_through_the_same_driver(tree):
    man, before = tree
    res = run.measure(manifest.Cell(man, "tiny-mlp-staged"), 2 ** 31 + 3, 0.3,
                      False, tiny.DEVICE)
    assert res["correct"] is True, res["compared"]
    assert list(res["compared"]) == [
        "loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
        "big_grad_norm_gap", "grad_angle.w1", "grad_angle.w2",
        "change_norm_gap"]
    assert res["metrics"]["train_images_per_s_per_chip"]["value"] > 0
    assert res["attempted"] >= 1
    for path, data in before.items():      # nothing that was there changed
        with open(path, "rb") as fh:
            assert fh.read() == data, path


def test_its_half_batch_fault_is_not_correct(tree):
    man, _ = tree

    def half_batch(prog):
        inner = prog.net._fit_batch
        prog.net._fit_batch = lambda x, y: inner(x[:8], y[:8])

    res = run.measure(manifest.Cell(man, "tiny-mlp-staged"), 11, 0.3, False,
                      tiny.DEVICE, planted=half_batch)
    assert res["correct"] is False


def test_its_mfu_reads_the_references_own_count(tree):
    man, _ = tree
    cell = manifest.Cell(man, "tiny-mlp-staged")
    assert "train_step_mfu" in {m["name"] for m in cell.per_layer()}
    reader = manifest.module_from("metrics", "train_step_mfu")
    flops = 3 * 2 * (12 * 32 + 32 * 5)
    got = reader.read({
        "cfg": cell.cfg, "chips": 1, "batch": 16,
        "peaks": {"flops_bf16": 1e6},
        "trace": {"window_s": 2.0, "module_s": {"jit_step": 1.0},
                  "module_n": {"jit_step": 10}}})
    assert got == pytest.approx(flops * 160 / (2.0 * 1e6) * 100.0)
