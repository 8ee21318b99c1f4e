"""ComputationGraph tests — ComputationGraphTest / graph-vertex gradcheck
parity (SURVEY.md §4: every vertex type exercised forward + gradient)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.autodiff.gradcheck import check_model_gradients
from deeplearning4j_tpu.data import DataSet, MultiDataSet
from deeplearning4j_tpu.nn import (
    ComputationGraph,
    ComputationGraphConfiguration,
    InputType,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.layers import (
    ConvolutionLayer,
    DenseLayer,
    GlobalPoolingLayer,
    OutputLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.updaters import Adam, Sgd
from deeplearning4j_tpu.nn.vertices import (
    ElementWiseVertex,
    L2NormalizeVertex,
    MergeVertex,
    ReshapeVertex,
    ScaleVertex,
    ShiftVertex,
    StackVertex,
    SubsetVertex,
    UnstackVertex,
)


def _two_branch_graph(updater=None):
    """in → dense1 → {branch a, branch b} → merge → out (3-class)."""
    return (
        NeuralNetConfiguration.builder()
        .seed(7)
        .updater(updater or Adam(0.01))
        .graph_builder()
        .add_inputs("in")
        .add_layer("d1", DenseLayer(n_in=4, n_out=8, activation="tanh"), "in")
        .add_layer("a", DenseLayer(n_in=8, n_out=6, activation="relu"), "d1")
        .add_layer("b", DenseLayer(n_in=8, n_out=6, activation="relu"), "d1")
        .add_vertex("merge", MergeVertex(), "a", "b")
        .add_layer("out", OutputLayer(n_in=12, n_out=3), "merge")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(4))
        .build()
    )


def _toy_data(rng, n=64, n_in=4, n_out=3):
    x = rng.normal(size=(n, n_in)).astype(np.float32)
    cls = (np.abs(x).sum(axis=1) * 2).astype(int) % n_out
    y = np.eye(n_out, dtype=np.float32)[cls]
    return x, y


def test_build_topo_and_shapes():
    net = ComputationGraph(_two_branch_graph()).init()
    assert net._shape_of["merge"] == (12,)
    assert net._shape_of["out"] == (3,)
    assert net.num_params() == (4 * 8 + 8) + 2 * (8 * 6 + 6) + (12 * 3 + 3)


def test_forward_output_shape(rng):
    net = ComputationGraph(_two_branch_graph()).init()
    x, _ = _toy_data(rng)
    out = net.output(x)
    assert out.shape == (64, 3)
    np.testing.assert_allclose(np.sum(np.asarray(out), axis=1), 1.0, atol=1e-5)


def test_fit_learns(rng):
    net = ComputationGraph(_two_branch_graph()).init()
    x, y = _toy_data(rng, n=256)
    s0 = net.score(x=x, y=y)
    for _ in range(150):
        net.fit(x, y)
    assert net.score(x=x, y=y) < s0 * 0.85


def test_residual_elementwise_add(rng):
    """Residual connection: out = dense2(relu(dense1(x)) + x)."""
    conf = (
        NeuralNetConfiguration.builder()
        .seed(3)
        .updater(Sgd(0.1))
        .graph_builder()
        .add_inputs("in")
        .add_layer("d1", DenseLayer(n_in=5, n_out=5, activation="relu"), "in")
        .add_vertex("res", ElementWiseVertex(op="add"), "d1", "in")
        .add_layer("out", OutputLayer(n_in=5, n_out=2), "res")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(5))
        .build()
    )
    net = ComputationGraph(conf).init()
    x, y = _toy_data(rng, n=32, n_in=5, n_out=2)
    # forward value check: res == relu(d1(x)) + x
    acts = net.feed_forward(x)
    manual = np.maximum(
        np.asarray(x) @ np.asarray(net.params["d1"]["W"]) + np.asarray(net.params["d1"]["b"]),
        0,
    ) + np.asarray(x)
    np.testing.assert_allclose(np.asarray(acts["res"]), manual, rtol=1e-5)
    # output() compiles a program that returns the output vertex alone (one
    # that returns every vertex keeps every activation alive: ResNet-50
    # B=256 exhausted a 16 GB chip, PR 21); feed_forward() returns them all
    ins = {"in": jnp.asarray(x)}
    out_acts, _ = jax.eval_shape(net._forward_train_jit, net.params,
                                 net.states, ins)
    assert set(out_acts) == {"out"} and set(acts) >= {"d1", "res", "out"}
    np.testing.assert_allclose(np.asarray(net.output(x)),
                               np.asarray(acts["out"]), rtol=1e-6)
    s0 = net.score(x=x, y=y)
    for _ in range(60):
        net.fit(x, y)
    assert net.score(x=x, y=y) < s0


def test_multi_input_multi_output(rng):
    conf = (
        NeuralNetConfiguration.builder()
        .seed(11)
        .updater(Adam(0.01))
        .graph_builder()
        .add_inputs("ina", "inb")
        .add_layer("da", DenseLayer(n_in=3, n_out=4, activation="tanh"), "ina")
        .add_layer("db", DenseLayer(n_in=2, n_out=4, activation="tanh"), "inb")
        .add_vertex("m", MergeVertex(), "da", "db")
        .add_layer("out1", OutputLayer(n_in=8, n_out=2), "m")
        .add_layer("out2", OutputLayer(n_in=8, n_out=3), "m")
        .set_outputs("out1", "out2")
        .set_input_types(InputType.feed_forward(3), InputType.feed_forward(2))
        .build()
    )
    net = ComputationGraph(conf).init()
    xa = rng.normal(size=(16, 3)).astype(np.float32)
    xb = rng.normal(size=(16, 2)).astype(np.float32)
    y1 = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 16)]
    y2 = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
    o1, o2 = net.output(xa, xb)
    assert o1.shape == (16, 2) and o2.shape == (16, 3)
    mds = MultiDataSet(features=[xa, xb], labels=[y1, y2])
    s0 = net.score(x=[xa, xb], y=[y1, y2])
    for _ in range(80):
        net.fit([mds])
    assert net.score(x=[xa, xb], y=[y1, y2]) < s0


def test_implicit_merge_on_multi_input_layer(rng):
    """A layer with 2 declared inputs gets an implicit MergeVertex (reference
    ComputationGraphConfiguration behavior)."""
    conf = (
        NeuralNetConfiguration.builder()
        .graph_builder()
        .add_inputs("a", "b")
        .add_layer("out", OutputLayer(n_in=5, n_out=2), "a", "b")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(3), InputType.feed_forward(2))
        .build()
    )
    net = ComputationGraph(conf).init()
    o = net.output(
        rng.normal(size=(4, 3)).astype(np.float32),
        rng.normal(size=(4, 2)).astype(np.float32),
    )
    assert o.shape == (4, 2)


def test_cnn_graph_with_pooling(rng):
    conf = (
        NeuralNetConfiguration.builder()
        .seed(5)
        .updater(Adam(0.005))
        .graph_builder()
        .add_inputs("img")
        .add_layer("c1", ConvolutionLayer(n_out=4, kernel_size=(3, 3), activation="relu"), "img")
        .add_layer("p1", SubsamplingLayer(kernel_size=(2, 2)), "c1")
        .add_layer("c2", ConvolutionLayer(n_out=8, kernel_size=(3, 3), activation="relu"), "p1")
        .add_vertex("gap", ScaleVertex(scale=1.0), "c2")
        .add_layer("pool", GlobalPoolingLayer(), "gap")
        .add_layer("out", OutputLayer(n_in=8, n_out=2), "pool")
        .set_outputs("out")
        .set_input_types(InputType.convolutional(8, 8, 1))
        .build()
    )
    net = ComputationGraph(conf).init()
    x = rng.normal(size=(6, 8, 8, 1)).astype(np.float32)
    assert net.output(x).shape == (6, 2)


@pytest.mark.parametrize(
    "vertex,n_inputs,in_shape,expected_shape",
    [
        (MergeVertex(), 2, (4,), (8,)),
        (ElementWiseVertex(op="add"), 2, (4,), (4,)),
        (ElementWiseVertex(op="subtract"), 2, (4,), (4,)),
        (ElementWiseVertex(op="product"), 2, (4,), (4,)),
        (ElementWiseVertex(op="average"), 3, (4,), (4,)),
        (ElementWiseVertex(op="max"), 2, (4,), (4,)),
        (SubsetVertex(from_idx=1, to_idx=2), 1, (4,), (2,)),
        (ScaleVertex(scale=2.5), 1, (4,), (4,)),
        (ShiftVertex(shift=1.0), 1, (4,), (4,)),
        (L2NormalizeVertex(), 1, (4,), (4,)),
        (ReshapeVertex(new_shape=(2, 2)), 1, (4,), (2, 2)),
    ],
)
def test_vertex_forward_and_shape(rng, vertex, n_inputs, in_shape, expected_shape):
    xs = [rng.normal(size=(3,) + in_shape).astype(np.float32) for _ in range(n_inputs)]
    out = vertex.apply(*[jnp.asarray(x) for x in xs])
    assert tuple(out.shape[1:]) == expected_shape
    assert vertex.output_shape(*[in_shape] * n_inputs) == expected_shape
    # differentiable through the vertex
    g = jax.grad(lambda *a: jnp.sum(vertex.apply(*a) ** 2))(*[jnp.asarray(x) for x in xs])
    assert np.all(np.isfinite(np.asarray(g)))


def test_stack_unstack(rng):
    a = rng.normal(size=(3, 4)).astype(np.float32)
    b = rng.normal(size=(3, 4)).astype(np.float32)
    stacked = StackVertex().apply(jnp.asarray(a), jnp.asarray(b))
    assert stacked.shape == (6, 4)
    back = UnstackVertex(index=1, num_stacked=2).apply(stacked)
    np.testing.assert_allclose(np.asarray(back), b)


def test_parallel_inference_serves_graph(rng):
    from deeplearning4j_tpu.parallel import ParallelInference

    net = ComputationGraph(_two_branch_graph()).init()
    pi = ParallelInference(net)
    x = rng.normal(size=(13, 4)).astype(np.float32)  # ragged vs 8 devices
    out = pi.output(x)
    assert out.shape == (13, 3)
    np.testing.assert_allclose(out, np.asarray(net.output(x)), rtol=2e-3, atol=1e-5)


def test_fit_multi_input_arrays(rng):
    conf = (
        NeuralNetConfiguration.builder()
        .graph_builder()
        .add_inputs("a", "b")
        .add_layer("out", OutputLayer(n_in=5, n_out=2), "a", "b")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(3), InputType.feed_forward(2))
        .build()
    )
    net = ComputationGraph(conf).init()
    xa = rng.normal(size=(4, 3)).astype(np.float32)
    xb = rng.normal(size=(4, 2)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 4)]
    net.fit([xa, xb], [y], epochs=2)
    assert np.isfinite(net.get_score())


def test_json_round_trip():
    conf = _two_branch_graph()
    s = conf.to_json()
    conf2 = ComputationGraphConfiguration.from_json(s)
    assert conf2.to_json() == s
    net = ComputationGraph(conf2).init()
    assert net._shape_of["out"] == (3,)


def test_graph_gradients_match_fd(rng):
    """fp64 central-difference gradcheck through merge + elementwise vertices
    (GradientCheckTestsComputationGraph parity)."""
    conf = (
        NeuralNetConfiguration.builder()
        .seed(13)
        .graph_builder()
        .add_inputs("in")
        .add_layer("d1", DenseLayer(n_in=3, n_out=4, activation="tanh"), "in")
        .add_layer("d2", DenseLayer(n_in=3, n_out=4, activation="sigmoid"), "in")
        .add_vertex("ew", ElementWiseVertex(op="product"), "d1", "d2")
        .add_vertex("mg", MergeVertex(), "ew", "d1")
        .add_layer("out", OutputLayer(n_in=8, n_out=2), "mg")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(3))
        .build()
    )
    net = ComputationGraph(conf).init()
    x = rng.normal(size=(5, 3))
    y = np.eye(2)[rng.integers(0, 2, 5)]

    def loss_fn(params):
        keys = {n.name: jax.random.PRNGKey(0) for n in net.topo if n.is_layer}
        loss, _ = net._loss(
            params, net.states, {"in": jnp.asarray(x)}, {"out": jnp.asarray(y)}, keys
        )
        return loss

    res = check_model_gradients(loss_fn, net.params)
    assert res.passed, repr(res)


def test_graph_mask_threading_and_fit_dataset(rng):
    """Sequence graph with attention: (B,T) masks reach mask-aware layers and
    the per-step loss; fit(DataSet) works (ComputationGraph mask parity)."""
    from deeplearning4j_tpu.data import DataSet
    from deeplearning4j_tpu.nn.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.recurrent import RnnOutputLayer

    gb = (NeuralNetConfiguration.builder().seed(0).updater(Adam(0.01))
          .graph_builder().add_inputs("in"))
    gb.add_layer("attn", SelfAttentionLayer(n_in=4, n_out=6, n_heads=2), "in")
    gb.add_layer("out", RnnOutputLayer(n_in=6, n_out=3, loss="mcxent",
                                       activation="softmax"), "attn")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(4, 5))
    net = ComputationGraph(gb.build()).init()

    x = rng.standard_normal((3, 5, 4)).astype(np.float32)
    mask = np.ones((3, 5), np.float32)
    mask[0, 3:] = 0
    # masked keys don't leak into valid positions
    y1 = np.asarray(net.output(x, mask=mask))
    x2 = x.copy()
    x2[0, 3:] += 50.0
    y2 = np.asarray(net.output(x2, mask=mask))
    np.testing.assert_allclose(y1[0, :3], y2[0, :3], atol=1e-4)

    ids = rng.integers(0, 3, size=(3, 5))
    labels = np.eye(3, dtype=np.float32)[ids]
    ds = DataSet(x, labels, features_mask=mask, labels_mask=mask.copy())
    s0 = net.score(ds)
    net.fit(ds, epochs=12)
    assert net.score(ds) < s0


def test_fit_compiles_the_train_step_once(rng):
    """No leaf of a fresh network is weakly typed, so the step traced at
    iteration 0 is the step that runs at iteration 1 and 2. BatchNorm's
    gamma/beta used to be ``jnp.full(shape, python_float)`` — weak f32 — and
    the flagship compiled its train step three times on the chip (PR 21)."""
    from deeplearning4j_tpu.nn.layers import BatchNormalization
    from deeplearning4j_tpu.util import get_watcher

    conf = (
        NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-3))
        .graph_builder().add_inputs("in")
        .add_layer("d1", DenseLayer(n_in=5, n_out=5, activation="relu"), "in")
        .add_layer("bn", BatchNormalization(), "d1")
        .add_layer("out", OutputLayer(n_in=5, n_out=2), "bn")
        .set_outputs("out").set_input_types(InputType.feed_forward(5))
        .build()
    )
    net = ComputationGraph(conf).init()
    leaves = jax.tree_util.tree_leaves(
        (net.params, net.states, net.opt_states))
    assert not [a for a in leaves if getattr(a, "weak_type", False)]
    x, y = _toy_data(rng, n=8, n_in=5, n_out=2)
    with get_watcher().scope() as s:
        for _ in range(3):
            net.fit(x, y)
        assert s.traces_of("ComputationGraph.train_step") == 1
