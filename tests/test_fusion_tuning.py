"""Fusion-boundary engineering tests (util/xla_tuning.py): selective-remat
policy registry, differentiable optimization barriers, config JSON round-trip
on both network types, and — the load-bearing invariant — policied train
steps being loss- AND gradient-equivalent to the unpolicied step (remat only
changes what XLA keeps live across fwd/bwd, never the arithmetic)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.autodiff.gradcheck import check_model_gradients
from deeplearning4j_tpu.nn import (
    InputType,
    MultiLayerNetwork,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.computation_graph import (
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.layers import (
    ConvolutionLayer,
    DenseLayer,
    OutputLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.util import xla_tuning
from deeplearning4j_tpu.zoo import ResNet50


# ---------------------------------------------------------------- registry
def test_policy_registry():
    assert xla_tuning.resolve_policy(None) == (False, None)
    assert xla_tuning.resolve_policy("none") == (False, None)
    wrap, pol = xla_tuning.resolve_policy("full")
    assert wrap and pol is None  # jax.checkpoint default: recompute all
    for name in ("save_conv", "save_conv_dots", "save_dots", "save_all"):
        wrap, pol = xla_tuning.resolve_policy(name)
        assert wrap and pol is not None
    with pytest.raises(ValueError, match="unknown remat policy"):
        xla_tuning.resolve_policy("nope")


def test_builder_rejects_unknown_policy():
    with pytest.raises(ValueError, match="unknown remat policy"):
        NeuralNetConfiguration.builder().remat_policy("typo_policy")


def test_env_default_remat_policy(monkeypatch):
    from deeplearning4j_tpu import config as cfg

    monkeypatch.setenv("DL4J_TPU_REMAT_POLICY", "save_conv")
    monkeypatch.setattr(cfg.Environment, "_instance", None)
    try:
        assert (NeuralNetConfiguration.builder()._remat_policy
                == "save_conv")
    finally:
        monkeypatch.setattr(cfg.Environment, "_instance", None)


# ---------------------------------------------------------------- barrier
def test_barrier_identity_and_gradient():
    tree = {"a": jnp.arange(4.0), "b": jnp.ones((2, 3))}
    out = xla_tuning.barrier(tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(tree[k]))

    def f(x, barrier):
        h = x * x
        if barrier:
            h = xla_tuning.barrier(h)
        return jnp.sum(jnp.sin(h))

    x = jnp.linspace(0.1, 2.0, 7)
    g_plain = jax.grad(f)(x, False)
    g_fenced = jax.grad(f)(x, True)
    np.testing.assert_allclose(np.asarray(g_fenced), np.asarray(g_plain),
                               rtol=1e-6)


# ----------------------------------------------------- MLN config round-trip
def _mln_conv_conf(policy=None, barriers=False, activation="relu"):
    b = NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.05))
    if policy is not None:
        b.remat_policy(policy)
    if barriers:
        b.stage_barriers(True)
    return (
        b.list()
        .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                activation=activation))
        .layer(SubsamplingLayer(kernel_size=(2, 2)))
        .stage_boundary()
        .layer(ConvolutionLayer(n_out=6, kernel_size=(3, 3),
                                activation=activation))
        .stage_boundary()
        .layer(DenseLayer(n_out=16, activation=activation))
        .layer(OutputLayer(n_in=16, n_out=3))
        .set_input_type(InputType.convolutional(8, 8, 1))
        .build()
    )


def test_mln_remat_config_json_roundtrip():
    conf = _mln_conv_conf(policy="save_conv", barriers=True)
    assert conf.remat_policy == "save_conv"
    assert conf.remat_stages == (2, 3)
    assert conf.stage_barriers is True
    s = conf.to_json()
    conf2 = MultiLayerConfiguration.from_json(s)
    assert conf2.remat_policy == "save_conv"
    assert conf2.remat_stages == (2, 3)
    assert conf2.stage_barriers is True
    assert conf2.to_json() == s
    # absent knobs stay off after a round-trip (old JSON keeps loading)
    plain = MultiLayerConfiguration.from_json(_mln_conv_conf().to_json())
    assert plain.remat_policy is None and plain.stage_barriers is False


def test_cg_remat_config_json_roundtrip():
    conf = ResNet50(num_classes=8, input_shape=(32, 32, 3),
                    remat_policy="save_conv", stage_barriers=True).conf()
    assert conf.remat_policy == "save_conv"
    assert conf.remat_stages == ("stem_pool", "res2c_out", "res3d_out",
                                 "res4f_out", "res5c_out")
    assert conf.stage_barriers is True
    s = conf.to_json()
    conf2 = ComputationGraphConfiguration.from_json(s)
    assert conf2.remat_policy == conf.remat_policy
    assert conf2.remat_stages == conf.remat_stages
    assert conf2.stage_barriers is True
    assert conf2.to_json() == s


def test_env_typo_remat_policy_fails_fast(monkeypatch):
    """A typo'd DL4J_TPU_REMAT_POLICY must fail at builder construction,
    not deep inside jit tracing of the first train step."""
    from deeplearning4j_tpu import config as cfg

    monkeypatch.setenv("DL4J_TPU_REMAT_POLICY", "save_convs")
    monkeypatch.setattr(cfg.Environment, "_instance", None)
    try:
        with pytest.raises(ValueError,
                           match="DL4J_TPU_REMAT_POLICY.*unknown"):
            NeuralNetConfiguration.builder()
    finally:
        monkeypatch.setattr(cfg.Environment, "_instance", None)


def test_cg_aux_output_inside_stage_rejected():
    """An output node that topologically precedes a stage boundary would be
    swallowed into the checkpointed stage — run as plain .apply() instead of
    compute_loss(), silently dropping its loss from training. Must refuse."""
    from deeplearning4j_tpu.nn import ComputationGraph, ComputationGraphConfiguration
    from deeplearning4j_tpu.nn.computation_graph import GraphBuilder

    gb = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.1))
          .remat_policy("full").graph_builder()
          .add_inputs("input")
          .add_layer("h1", DenseLayer(n_in=4, n_out=8), "input")
          .add_layer("aux", OutputLayer(n_in=8, n_out=2), "h1")
          .add_layer("h2", DenseLayer(n_in=8, n_out=8), "h1")
          .stage_boundary("h2")
          .add_layer("main", OutputLayer(n_in=8, n_out=2), "h2")
          .set_outputs("aux", "main"))
    with pytest.raises(ValueError, match="aux.*inside remat stage"):
        ComputationGraph(gb.build())


def test_ops_tags_match_policy_names():
    """ops/nn.py conv/dot tags and the xla_tuning policy targets are one
    source — drift would silently degrade 'save_conv' to full recompute."""
    from deeplearning4j_tpu.ops import nn as ops_nn

    assert ops_nn._CONV_OUT is xla_tuning.CONV_OUT
    assert ops_nn._DOT_OUT is xla_tuning.DOT_OUT


def test_cg_bad_stage_boundary_rejected():
    from deeplearning4j_tpu.nn import ComputationGraph

    conf = ResNet50(num_classes=8, input_shape=(32, 32, 3)).conf()
    conf.remat_policy = "save_conv"
    conf.remat_stages = ("not_a_node",)
    with pytest.raises(ValueError, match="not a node"):
        ComputationGraph(conf)
    conf.remat_stages = ("output",)
    with pytest.raises(ValueError, match="output layer"):
        ComputationGraph(conf)


# ------------------------------------------------- MLN step equivalence
def _mln_loss_and_grad(conf, x, y):
    net = MultiLayerNetwork(conf).init()
    keys = list(jax.random.split(jax.random.PRNGKey(0), len(net.layers)))

    def loss_fn(params):
        # follow the params' dtype so the x64 gradcheck feeds fp64 activations
        dt = jax.tree_util.tree_leaves(params)[0].dtype
        loss, _ = net._loss(params, net.states, jnp.asarray(x, dt),
                            jnp.asarray(y, dt), keys)
        return loss

    return net, loss_fn, float(loss_fn(net.params)), jax.grad(loss_fn)(
        net.params)


@pytest.mark.parametrize("policy,barriers", [
    ("full", False),
    ("save_conv", False),
    ("save_conv_dots", False),
    ("save_all", False),
    (None, True),
    ("save_conv", True),
])
def test_mln_policied_step_matches_plain(rng, policy, barriers):
    """Same seed → same params; the policied loss and every parameter
    gradient must match the unpolicied step (remat/barriers change the
    schedule, not the math)."""
    x = rng.normal(size=(4, 8, 8, 1)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 4)]
    _, _, base_loss, base_grad = _mln_loss_and_grad(_mln_conv_conf(), x, y)
    net, _, pol_loss, pol_grad = _mln_loss_and_grad(
        _mln_conv_conf(policy=policy, barriers=barriers), x, y)
    assert net._segments is not None  # the fusion-boundary path actually ran
    np.testing.assert_allclose(pol_loss, base_loss, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6),
        pol_grad, base_grad)


def test_mln_policied_step_gradcheck(rng):
    """Finite-difference gradcheck THROUGH the remat path — the policied
    train step is gradcheck-equivalent, not just jax.grad-consistent."""
    x = rng.normal(size=(2, 8, 8, 1)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 2)]
    # tanh (the whole-network gradcheck idiom): relu kinks break the central
    # difference, and _loss returns the scalar astype(float32) (train-step
    # contract) so eps must also clear the fp32 loss-rounding floor
    net, loss_fn, _, _ = _mln_loss_and_grad(
        _mln_conv_conf(policy="save_conv", barriers=True, activation="tanh"),
        x, y)
    res = check_model_gradients(loss_fn, net.params, eps=1e-3,
                                max_rel_error=1e-2, min_abs_error=1e-4)
    assert res.passed, repr(res)


def test_mln_bad_stage_boundary_rejected():
    conf = _mln_conv_conf(policy="save_conv")
    conf = MultiLayerConfiguration.from_json(conf.to_json())
    conf.remat_stages = (99,)
    with pytest.raises(ValueError, match="out of range"):
        MultiLayerNetwork(conf)


# -------------------------------------------------- flagship equivalence
def _flagship_loss(policy, barriers, x, y):
    net = ResNet50(num_classes=8, input_shape=(32, 32, 3),
                   remat_policy=policy, stage_barriers=barriers).init()
    keys = {n.name: k for n, k in zip(
        [n for n in net.topo if n.is_layer],
        jax.random.split(jax.random.PRNGKey(0),
                         sum(n.is_layer for n in net.topo)))}

    def loss_fn(params):
        loss, _ = net._loss(params, net.states, {"input": jnp.asarray(x)},
                            {"output": jnp.asarray(y)}, keys)
        return loss

    return net, loss_fn


# slow: over a minute by itself (the ResNet-50 flagship graph built and
# compiled under every policy). In tier-1 test_mln_policied_step_matches_plain
# and test_mln_policied_step_gradcheck above hold the remat-policy seam on
# a small net
@pytest.mark.slow
def test_flagship_policied_loss_matches_plain(rng):
    """Tiny-config ResNet-50 (the flagship graph shape, stage boundaries at
    stem/res2–res5): every registered policy and the barrier variant produce
    the unpolicied loss exactly."""
    x = rng.normal(size=(2, 32, 32, 3)).astype(np.float32)
    y = np.eye(8, dtype=np.float32)[rng.integers(0, 8, 2)]
    base_net, base_fn = _flagship_loss(None, False, x, y)
    base = float(base_fn(base_net.params))
    for policy, barriers in [("full", False), ("save_conv", False),
                             ("save_conv", True), (None, True)]:
        net, fn = _flagship_loss(policy, barriers, x, y)
        assert net._segments is not None
        np.testing.assert_allclose(float(fn(net.params)), base, rtol=1e-5)


# slow: most of a minute by itself; test_mln_policied_step_gradcheck is the
# tier-1 test of gradients through policied segments
@pytest.mark.slow
def test_flagship_policied_grad_matches_plain(rng):
    """Full jax.grad through the segmented flagship graph equals the plain
    gradient for the r6 sweep's leading candidate."""
    x = rng.normal(size=(2, 32, 32, 3)).astype(np.float32)
    y = np.eye(8, dtype=np.float32)[rng.integers(0, 8, 2)]
    base_net, base_fn = _flagship_loss(None, False, x, y)
    pol_net, pol_fn = _flagship_loss("save_conv", True, x, y)
    g_base = jax.grad(base_fn)(base_net.params)
    g_pol = jax.grad(pol_fn)(pol_net.params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6),
        g_pol, g_base)
