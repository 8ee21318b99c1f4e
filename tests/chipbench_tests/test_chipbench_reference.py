"""Each plain reference against the program at a small size on the CPU: the
``zoo.ResNet50`` train step, and prefill-then-decode through the paged cache
against the reference's full forward."""

import numpy as np
import pytest

import jax.numpy as jnp

from chipbench import checks, serve
from chipbench.manifest import module_from

import chipbench_tiny as tiny


KEEP = tuple(tiny.RESNET["limits"]["grad_angle"])


@pytest.fixture(scope="module")
def resnet():
    """The program's first three steps and the reference's, float32 both."""
    ref = module_from("reference", "resnet50")
    builder = module_from("builders", "zoo.ResNet50")
    cfg = dict(tiny.RESNET, image_size=64, per_chip_batch=16)
    w = ref.make_weights(3, cfg)
    net = builder.build(cfg)
    builder.load(net, w)
    rng = np.random.default_rng(0)
    batches = [(jnp.asarray(rng.normal(size=(16, 64, 64, 3)), jnp.float32),
                jnp.asarray(np.eye(10, dtype=np.float32)[
                    rng.integers(0, 10, 16)])) for _ in range(3)]
    losses = []
    for i, (x, y) in enumerate(batches):
        net._fit_batch(x, y)
        losses.append(float(net.score_value))
        if i == 0:
            m = builder.adam_m(net)
            m1 = {k: float(jnp.linalg.norm(a.ravel())) / 0.1
                  for k, a in m.items()}
            first = {k: jnp.array(m[k], copy=True) for k in KEEP}
    params = builder.export(net.params)
    got = {"losses": losses, "grad_norms": m1, "first_grads": first,
           "change_norms": {
               k: float(jnp.linalg.norm((params[k] - w[k]).ravel()))
               for k in w}}
    return ref, w, batches, got, ref.follow(w, batches, keep=KEEP)


def test_builder_names_every_leaf_of_the_program(resnet):
    ref, w, _b, got, want = resnet
    assert set(got["grad_norms"]) == set(w) == set(want["grad_norms"])
    assert len(w) == 3 * 53 + 2     # 53 convolutions with BN, the classifier


def test_first_loss_agrees_to_float32_rounding(resnet):
    _r, _w, _b, got, want = resnet
    assert abs(got["losses"][0] - want["losses"][0]) < 1e-4 * want["losses"][0]


def test_first_gradient_agrees_leaf_by_leaf(resnet):
    _r, _w, _b, got, want = resnet
    assert checks.worst_leaf_gap(got["grad_norms"],
                                 want["grad_norms"]) < 0.03


def test_three_adam_steps_move_every_leaf_alike(resnet):
    _r, _w, _b, got, want = resnet
    assert checks.worst_leaf_gap(got["change_norms"],
                                 want["change_norms"]) < 0.08
    assert min(want["change_norms"].values()) > 0


def test_the_program_is_correct_by_the_harness_own_comparison(resnet):
    _r, _w, _b, got, want = resnet
    ns = checks.training_numbers(got, want, tiny.RESNET["limits"])
    assert checks.verdict(ns), ns


@pytest.mark.parametrize("leaf", KEEP)
def test_each_kept_leafs_gradient_points_as_the_references(resnet, leaf):
    _r, _w, _b, got, want = resnet
    assert want["first_grads"][leaf].shape == got["first_grads"][leaf].shape
    assert checks.angle(got["first_grads"][leaf],
                        want["first_grads"][leaf]) < 5e-3


@pytest.mark.parametrize("how", [{"lower": "fp8"}, {"fault": "half_batch"}],
                         ids=["fp8_control", "half_batch_fault"])
def test_control_and_fault_are_not_correct_under_the_cells_limits(resnet,
                                                                  how):
    ref, w, batches, _got, want = resnet
    other = ref.follow(w, batches, keep=KEEP, **how)
    ns = checks.training_numbers(other, want, tiny.RESNET["limits"])
    assert not checks.verdict(ns)
    assert [n["name"] for n in ns if n["value"] > n["limit"]]


def test_half_a_batch_repeated_is_half_a_batch(resnet):
    """The fault's rows: the second half repeats the first, which has the
    batch statistics, loss and gradient of the first half alone."""
    ref, w, batches, _got, _want = resnet
    bad = ref.follow(w, batches[:1], fault="half_batch")
    half = ref.follow(w, [(batches[0][0][:8], batches[0][1][:8])])
    assert bad["losses"][0] == pytest.approx(half["losses"][0], rel=1e-5)
    assert checks.worst_leaf_gap(bad["grad_norms"],
                                 half["grad_norms"]) < 0.02


def test_batches_come_from_the_seed_in_the_compute_type(resnet):
    ref = resnet[0]
    cfg = dict(tiny.RESNET, compute_dtype="bfloat16")
    a = ref.make_batches(2 ** 31 + 5, cfg, 3, 4)
    b = ref.make_batches(2 ** 31 + 5, cfg, 3, 4)
    c = ref.make_batches(2 ** 31 + 6, cfg, 3, 4)
    assert len(a) == 3 and a[0][0].shape == (4, 64, 64, 3)
    assert a[0][0].dtype == jnp.bfloat16 and a[0][1].shape == (4, 10)
    assert all(jnp.array_equal(x, u) and jnp.array_equal(y, v)
               for (x, y), (u, v) in zip(a, b))
    assert not jnp.array_equal(a[0][0], c[0][0])
    assert not jnp.array_equal(a[0][0], a[1][0]), "the batches all differ"
    assert float(jnp.sum(a[0][1])) == 4.0


@pytest.fixture(scope="module")
def decoder():
    ref = module_from("reference", "bert_decoder")
    builder = module_from("builders", "zoo.Bert.large")
    cfg = tiny.BERT
    w = ref.make_weights(5, cfg)
    net = builder.build(cfg)
    builder.load(net, w)
    return ref, cfg, w, net


def test_paged_prefill_then_decode_matches_the_full_forward(decoder):
    from deeplearning4j_tpu.serving.generate import Generator

    ref, cfg, w, net = decoder
    gen = Generator(net, max_length=64, batch_buckets=(1, 2, 4),
                    prefill_buckets=(16, 32), paged=True, block_size=8)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg["vocab_size"], size=n).tolist()
               for n in (5, 17, 30)]
    served = gen.generate(prompts, max_new_tokens=8)
    sample = [{"prompt": p, "tokens": t} for p, t in zip(prompts, served)]
    gaps = serve.served_gaps(ref, w, cfg, sample, rows_per_block=4)
    assert gaps.shape == (24,)
    assert gaps.max() < 1e-4, "every served token is the reference's best"


def test_an_altered_token_shows_as_a_gap(decoder):
    ref, cfg, w, _net = decoder
    prompt = list(range(1, 12))
    toks, pos, _ = serve.check_inputs(cfg, [{"prompt": prompt,
                                              "tokens": [0] * 4}])
    lg = ref.logits_at(w, jnp.asarray(toks), jnp.asarray(pos),
                       n_heads=cfg["num_attention_heads"])
    best = int(jnp.argmax(lg[0, 0]))
    wrong = (best + 1) % cfg["vocab_size"]
    gaps = serve.served_gaps(
        ref, w, cfg, [{"prompt": prompt, "tokens": [wrong, 1, 2, 3]}],
        rows_per_block=1)
    assert gaps[0] > 1e-3


def test_reference_is_causal_and_ignores_right_padding(decoder):
    ref, cfg, w, _net = decoder
    heads = cfg["num_attention_heads"]
    a = np.zeros((1, 64), np.int32)
    a[0, :10] = np.arange(1, 11)
    b = a.copy()
    b[0, 10:] = 7
    pos = jnp.asarray([[3, 9]])
    la = ref.logits_at(w, jnp.asarray(a), pos, n_heads=heads)
    lb = ref.logits_at(w, jnp.asarray(b), pos, n_heads=heads)
    assert jnp.array_equal(la, lb)


def test_bfloat16_control_moves_the_logits(decoder):
    ref, cfg, w, _net = decoder
    heads = cfg["num_attention_heads"]
    toks = jnp.asarray(np.arange(1, 65, dtype=np.int32)[None])
    pos = jnp.asarray(np.arange(0, 64, dtype=np.int32)[None])
    full = ref.logits_at(w, toks, pos, n_heads=heads)
    low = ref.logits_at(w, toks, pos, n_heads=heads, dtype=jnp.bfloat16)
    assert 1e-4 < float(jnp.max(jnp.abs(full - low))) < 1.0


def test_reference_modules_import_nothing_of_the_program():
    import os
    from chipbench import manifest

    for name in ("resnet50.py", "bert_decoder.py"):
        with open(os.path.join(manifest.HERE, "reference", name)) as f:
            src = f.read()
        assert "import deeplearning4j_tpu" not in src
        assert "from deeplearning4j_tpu" not in src
