"""Olmo Hybrid on the serving path, at a small size on the CPU (hidden 64,
one whole period: three Gated DeltaNet layers with keys of 32 and values of
64, whose state slot packs two heads as the published 96/192 heads are
packed, and a full layer with QK-norm; OLMo 2's post-norm block; seeded
weights): the program against the benchmark's plain reference. The two
delta-rule kernels at the published head widths are tested in
test_gdn_kernels.py.

Everything runs in float32 at ``highest``, so the tolerances are those of
float32 sums taken in another order: 2e-4 on logits of size 1, 1e-4 on one
block's outputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.manifest import module_from
from deeplearning4j_tpu.nn.decoder import HybridDecoderBlock, rms_norm
from deeplearning4j_tpu.serving import ServingModel
from deeplearning4j_tpu.serving.generate import Generator
from deeplearning4j_tpu.zoo import OlmoHybrid

L, F = "linear_attention", "full_attention"
CFG = dict(
    hidden_size=64, intermediate_size=128, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=4, layer_types=[L, L, L, F, L],
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=32,
    linear_value_head_dim=64, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rms_norm_eps=1e-6, vocab_size=96,
    max_position_embeddings=96, param_dtype="float32")
PROMPTS = [[5, 6, 7, 8, 9, 10, 11], [3] * 20, [1, 2, 3]]


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def ref():
    return module_from("reference", "olmo_hybrid")


@pytest.fixture(scope="module")
def served(ref):
    """(weights, generator) of the small model, built as the benchmark
    builds it: the builder's net and the reference's weights, with a slow
    decay and beta near its top of 2, so that a state carries far and its
    negative eigenvalues show."""
    builder = module_from("builders", "zoo.OlmoHybrid")
    w = ref.make_weights(3, CFG)
    for p in w["layers"]:
        if "Wa" in p:
            p["A_log"] = p["A_log"] - 3.0
            p["Wb"] = p["Wb"] * 20.0
    net = builder.build(CFG)
    builder.load(net, w)
    gen = Generator(net, max_length=96, batch_buckets=(4,),
                    prefill_buckets=(32,), block_size=8,
                    model_id="olmo-tiny")
    return w, gen


def _reference_logits(ref, w, prompts, served_tokens):
    new = len(served_tokens[0])
    toks = np.zeros((len(prompts), 48), np.int32)
    pos = np.zeros((len(prompts), new), np.int32)
    for i, (p, o) in enumerate(zip(prompts, served_tokens)):
        seq = list(p) + list(o[:-1])
        toks[i, :len(seq)] = seq
        pos[i] = len(p) - 1 + np.arange(new)
    return ref.logits_at(w, jnp.asarray(toks), jnp.asarray(pos))


# ------------------------------------------------- program against reference
def test_the_zoo_model_is_the_published_one_and_its_tiny_holds_a_period():
    m = OlmoHybrid()
    assert (m.vocab_size, m.hidden_size, len(m.layer_types), m.n_heads,
            m.n_kv_heads, m.linear_heads, m.key_head_dim, m.value_head_dim,
            m.conv_size, m.ffn_size, m.eps) == \
        (100352, 3840, 32, 30, 30, 30, 96, 192, 4, 11008, 1e-6)
    assert [i for i, t in enumerate(m.layer_types) if t == F] == \
        list(range(3, 32, 4))
    net = OlmoHybrid.tiny().network()
    blocks = net.layers[1:-1]
    assert [b.mixer for b in blocks] == ["gdn", "gdn", "gdn", "gqa"]
    assert all(b.norm == "post" for b in blocks) and blocks[3].qk_norm
    assert (blocks[0].head_dim, blocks[0]._dv, blocks[0]._pack) == (32, 64, 2)
    assert not net.layers[-1].tied and not net.params        # no init()


@pytest.mark.parametrize("layer", [0, 3], ids=["gdn", "gqa-qk-norm"])
def test_one_blocks_apply_is_the_references_layer(ref, served, layer):
    w, gen = served
    x = jax.random.normal(jax.random.PRNGKey(layer), (3, 24, 64))
    got, _ = gen.blocks[layer].apply(gen.net.params[layer + 1], {}, x)
    want = ref._layer(w["layers"][layer], x, w["dims"], None)
    assert float(jnp.abs(want - x).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_the_whole_net_is_the_references_forward(ref, served):
    w, gen = served
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 96, (3, 24)),
                       jnp.int32)
    x, _ = gen.emb.apply(gen.net.params[0], {}, toks)
    for blk, p in zip(gen.blocks, gen.net.params[1:-1]):
        x, _ = blk.apply(p, {}, x)
    got = gen.head._logits(gen.net.params[-1], x)
    want = ref.logits_at(w, toks, jnp.broadcast_to(jnp.arange(24), (3, 24)))
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_prefill_then_decode_matches_the_references_full_forward(ref,
                                                                 served):
    """Prefill, then 11 decode steps through the packed state slots and the
    paged K/V rows: every served token is the reference's own pick, its
    logit the reference's best to 2e-4; and the O(T^2) oracle serves the
    same."""
    w, gen = served
    out = gen.generate(PROMPTS, max_new_tokens=12)
    lg = _reference_logits(ref, w, PROMPTS, out)
    gap = jnp.max(lg, -1) - jnp.take_along_axis(
        lg, jnp.asarray(out)[..., None], -1)[..., 0]
    assert float(gap.max()) <= 2e-4
    assert out == gen.generate_full_recompute(PROMPTS, max_new_tokens=12)
    ok, detail = gen.pool.conservation()
    assert ok, detail
    stats = gen.pool_stats()
    assert stats["gdn_prefill_chunk_share"] and stats["gdn_decode_state_share"]


def test_served_through_the_model_server_objects(served):
    """ServingModel, as chipbench/serve.py constructs it, serves the net; a
    slot holds the two heads' states side by side on the lanes and the
    three convolutions' tail, a row ``[k | v]`` of the four K/V heads."""
    _, gen = served
    model = ServingModel(gen.net, "olmo", kind="generate", paged=True,
                         block_size=8, max_length=96,
                         bucketing="batch=4;seq=32")
    assert model.generator.generate(PROMPTS, max_new_tokens=4) == \
        [r[:4] for r in gen.generate(PROMPTS, max_new_tokens=12)]
    d = model.describe()["kv_pool"]
    assert d["recurrent"] is True and d["state_slots_total"] == 4
    # 3 linear layers x (2 heads x 32 x 64 + 3 x (2 x 64 + 2 x 64)) x 4 B
    assert d["bytes_by_kind"]["state"] == 4 * 3 * (2 * 32 * 64 + 3 * 256) * 4
    shapes = [{n: a.shape[1:] for n, a in p.items()}
              for p in model.generator.pool.pools]
    assert shapes[0] == {"state": (1, 32, 128), "conv": (3, 256)}
    assert shapes[3] == {"rows": (4 * 2 * 16,)}


def test_the_post_norm_block_adds_normed_sublayers():
    """``norm="post"``: the mixer and the feed-forward read the residual
    stream as it is, and what each adds is RMS-normed by its own scale."""
    blk = HybridDecoderBlock(hidden_size=32, mixer="gqa", n_heads=2,
                             n_kv_heads=2, head_dim=16, ffn_size=32,
                             norm="post", qk_norm=True)
    pre = HybridDecoderBlock(hidden_size=32, mixer="gqa", n_heads=2,
                             n_kv_heads=2, head_dim=16, ffn_size=32,
                             qk_norm=True)
    p, _ = blk.initialize(jax.random.PRNGKey(0), None)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    mask = jnp.ones((2, 8))
    a, _ = blk._gqa_expanded(p, x, mask)
    h = x + rms_norm(a, p["norm1"], blk.eps)
    ffn = lambda h: (jax.nn.silu(h @ p["Wgate"]) * (h @ p["Wup"])) \
        @ p["Wdown"]
    want = h + rms_norm(ffn(h), p["norm2"], blk.eps)
    got, _ = blk.apply(p, {}, x)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.abs(got - pre.apply(p, {}, x)[0]).max()) > 0.1
