"""Jamba on the serving path, at a small size on the CPU (hidden 64, one whole
period: Mamba, attention with 4 heads over 1 K/V head, Mamba, Mamba; seeded
weights): the program against the benchmark's plain reference, the forms of
the selective scan, grouped-query paged attention against the dense softmax,
the tied head, and the two kinds of cache in one pool manager.

Everything runs in float32 at ``highest``, so the tolerances are those of
float32 sums taken in another order: 2e-4 on logits of size 1, 1e-5 on one
scan's outputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.manifest import module_from
from deeplearning4j_tpu.nn.decoder import (HybridDecoderBlock,
                                           NormedLogitsLayer)
from deeplearning4j_tpu.ops import attention as attn_ops
from deeplearning4j_tpu.ops import kda, ssm
from deeplearning4j_tpu.serving import ServingModel
from deeplearning4j_tpu.serving.generate import Generator
from deeplearning4j_tpu.zoo import Jamba

CFG = dict(
    attn_layer_offset=1, attn_layer_period=4, hidden_size=64,
    intermediate_size=128, mamba_conv_bias=True, mamba_d_conv=4,
    mamba_d_state=8, mamba_dt_rank=8, mamba_expand=2, num_attention_heads=4,
    num_key_value_heads=1, head_dim=16, num_hidden_layers=4,
    rms_norm_eps=1e-6, vocab_size=96, max_position_embeddings=96,
    param_dtype="float32", tie_word_embeddings=True)
PROMPTS = [[5, 6, 7, 8, 9, 10, 11], [3] * 20, [1, 2, 3]]


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def ref():
    return module_from("reference", "jamba")


@pytest.fixture(scope="module")
def served(ref):
    """(weights, generator) of the small model, built as the benchmark
    builds it: the builder's net, the reference's weights, the head tied."""
    builder = module_from("builders", "zoo.Jamba")
    w = ref.make_weights(3, CFG)
    for p in w["layers"]:     # a state that tokens far back still show in
        if "A_log" in p:
            p["A_log"] = p["A_log"] - 2.0
    net = builder.build(CFG)
    builder.load(net, w)
    # a model id of its own: the process's counters are labelled by it
    gen = Generator(net, max_length=96, batch_buckets=(4,),
                    prefill_buckets=(32,), block_size=8,
                    model_id="jamba-tiny")
    return w, gen


def _reference_logits(ref, w, prompts, served_tokens):
    new = len(served_tokens[0])
    toks = np.zeros((len(prompts), 48), np.int32)
    pos = np.zeros((len(prompts), new), np.int32)
    for i, (p, o) in enumerate(zip(prompts, served_tokens)):
        seq = list(p) + list(o[:-1])
        toks[i, :len(seq)] = seq
        pos[i] = len(p) - 1 + np.arange(new)
    return ref.logits_at(w, jnp.asarray(toks), jnp.asarray(pos), n_heads=4)


# ------------------------------------------------- program against reference
def test_the_zoo_model_is_the_published_one_and_its_tiny_holds_a_period():
    m = Jamba()
    assert (m.vocab_size, m.hidden_size, m.n_layers, m.n_heads, m.n_kv_heads,
            m.head_dim, m.ffn_size, m.d_state, m.d_conv, m.dt_rank,
            m.expand) == (65536, 2560, 28, 20, 1, 128, 8192, 16, 4, 160, 2)
    assert [i for i in range(28) if m.is_attention(i)] == [7, 21]
    net = Jamba.tiny().network()
    assert [b.mixer for b in net.layers[1:-1]] == \
        ["mamba", "gqa", "mamba", "mamba"]
    assert net.layers[-1].tied and not net.params      # no init()


@pytest.mark.parametrize("layer", [0, 1], ids=["mamba", "gqa"])
def test_one_blocks_apply_is_the_references_layer(ref, served, layer):
    w, gen = served
    x = jax.random.normal(jax.random.PRNGKey(layer), (3, 24, 64))
    got, _ = gen.blocks[layer].apply(gen.net.params[layer + 1], {}, x)
    want = ref._layer(w["layers"][layer], x, w["dims"], None)
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
    """Prefill, then 11 decode steps through the state slots and the paged
    K/V rows: every served token is the reference's own pick, its logit the
    reference's best to 2e-4; and the O(T^2) oracle serves the same."""
    w, gen = served
    out = gen.generate(PROMPTS, max_new_tokens=12)
    lg = _reference_logits(ref, w, PROMPTS, out)
    gap = jnp.max(lg, -1) - jnp.take_along_axis(
        lg, jnp.asarray(out)[..., None], -1)[..., 0]
    assert float(gap.max()) <= 2e-4
    assert out == gen.generate_full_recompute(PROMPTS, max_new_tokens=12)
    ok, detail = gen.pool.conservation()
    assert ok, detail


def test_served_through_the_model_server_objects(served):
    """ServingModel, as chipbench/serve.py constructs it, serves the net;
    a slot is the state and the flat tail, a row ``[k | v]``."""
    _, gen = served
    model = ServingModel(gen.net, "jamba", kind="generate", paged=True,
                         block_size=8, max_length=96,
                         bucketing="batch=4;seq=32")
    assert model.generator.generate(PROMPTS, max_new_tokens=4) == \
        [r[:4] for r in gen.generate(PROMPTS, max_new_tokens=12)]
    d = model.describe()["kv_pool"]
    assert d["recurrent"] is True and d["state_slots_total"] == 4
    # 3 Mamba layers x (8 x 128 states + 3 x 128 inputs) x 4 B, 4 slots
    assert d["bytes_by_kind"]["state"] == 4 * 3 * (8 * 128 + 3 * 128) * 4
    shapes = {n: a.shape[1:] for p in model.generator.pool.pools
              for n, a in p.items()}
    assert shapes == {"state": (8, 128), "conv": (3 * 128,), "rows": (32,)}
    model.generator.pool.pools = None


def test_a_slot_at_the_published_sizes_is_10_1_mb():
    blk = Jamba().conf().layers[1]
    assert blk.mixer == "mamba"
    pool = jax.eval_shape(lambda: blk.init_pool(2))
    per_slot = sum(int(np.prod(a.shape[1:])) * 4 for a in pool.values())
    assert per_slot == 327680 + 61440
    assert round(26 * per_slot / 1e6, 1) == 10.1
    row = jax.eval_shape(lambda: Jamba().conf().layers[8].init_pool(2))
    assert row["rows"].shape == (2, 256)      # [k | v]: two whole lane tiles


# ------------------------------------------------------- the selective scan
def _scan_inputs(key, b, t, ch, n):
    ks = jax.random.split(key, 8)
    x = jax.random.normal(ks[0], (b, t, ch))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, ch)) - 2)
    a = -jnp.exp(jax.random.normal(ks[2], (ch, n)))
    bm, cm = (jax.random.normal(k, (b, t, n)) for k in ks[3:5])
    d = jax.random.normal(ks[5], (ch,))
    s = jax.random.normal(ks[6], (b, n, ch))
    z = jax.random.normal(ks[7], (b, t, ch))
    return x, dt, a, bm, cm, d, s, z


def _recurrence(x, dt, a, bm, cm, d, s, lengths, z):
    """The equations a token at a time in numpy float64, a row at a time,
    the state (channels, states) as the papers write it."""
    x, dt, a, bm, cm, d, s, z = (np.asarray(v, np.float64)
                                 for v in (x, dt, a, bm, cm, d, s, z))
    y = np.zeros_like(x)
    s = np.swapaxes(s, 1, 2).copy()
    for i in range(x.shape[0]):
        for t in range(int(lengths[i])):
            s[i] = np.exp(dt[i, t][:, None] * a) * s[i] \
                + (dt[i, t] * x[i, t])[:, None] * bm[i, t][None, :]
            y[i, t] = (s[i] @ cm[i, t] + d * x[i, t]) \
                * z[i, t] / (1 + np.exp(-z[i, t]))
    return y, np.swapaxes(s, 1, 2)


FORMS = {"xla": ssm._selective_scan_xla,
         "kernel": lambda *a: ssm._selective_scan_pallas(*a, interpret=True)}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_scan_is_the_recurrence_on_ragged_rows(form):
    """Lengths that end inside a chunk of 128 and inside a tile of 8, a
    full row, and a row of length 0, whose state is untouched."""
    x, dt, a, bm, cm, d, s, z = _scan_inputs(jax.random.PRNGKey(0), 4, 140,
                                             256, 8)
    lengths = jnp.asarray([140, 0, 77, 128])
    y, s1 = FORMS[form](x, dt, a.T, bm, cm, d, s, lengths, z)
    want_y, want_s = _recurrence(x, dt, a, bm, cm, d, s, lengths, z)
    np.testing.assert_allclose(y, want_y, atol=1e-5 * np.abs(want_y).max())
    np.testing.assert_allclose(s1, want_s, atol=1e-5)
    np.testing.assert_array_equal(s1[1], s[1])
    assert not np.asarray(y[1]).any() and not np.asarray(y[2, 77:]).any()


def test_the_scan_kernel_is_the_xla_form_without_lengths():
    x, dt, a, bm, cm, d, s, z = _scan_inputs(jax.random.PRNGKey(1), 2, 130,
                                             128, 16)
    y0, s0 = ssm._selective_scan_xla(x, dt, a.T, bm, cm, d, s, None, z)
    y1, s1 = ssm._selective_scan_pallas(x, dt, a.T, bm, cm, d, s, None, z,
                                        interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-5 * float(jnp.abs(y0).max()))
    np.testing.assert_allclose(s1, s0, atol=1e-5)


def test_the_entry_takes_the_kernel_where_the_chip_would(monkeypatch):
    x, dt, a, bm, cm, d, s, z = _scan_inputs(jax.random.PRNGKey(2), 2, 16,
                                             128, 8)
    seen = []
    monkeypatch.setattr(ssm, "_selective_scan_pallas",
                        lambda *args: seen.append("scan") or args[0:2])
    monkeypatch.setattr(ssm, "_selective_step_pallas",
                        lambda *args: seen.append("step") or (args[0], 0))
    live = jnp.ones((2, 1), bool)
    args = (x[:, :1], dt[:, :1], a, bm[:, :1], cm[:, :1], d,
            jnp.zeros((3, 8, 128)), jnp.asarray([1, 2]), live, z[:, :1])
    ssm.selective_scan(x, dt, a, bm, cm, d, s, None, z)
    ssm.selective_step_paged(*args)
    assert seen == []                                 # a CPU: the XLA forms
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ssm.selective_scan(x, dt, a, bm, cm, d, s, None, z)
    ssm.selective_step_paged(*args)
    assert seen == ["scan", "step"]


# ---------------------------------------------- the decode step in the pool
def _step_inputs(key, b, w, ch, n, slots):
    x, dt, a, bm, cm, d, _, z = _scan_inputs(key, b, w, ch, n)
    pool = jax.random.normal(jax.random.fold_in(key, 9), (slots, n, ch))
    return x, dt, a, bm, cm, d, pool, z


@pytest.mark.parametrize("rows", [11, 16], ids=["11-rows", "16-rows"])
def test_the_step_kernel_is_the_step_on_the_named_slots(rows):
    """Live rows step their own slot in place; a dead row (slot 0) reads 0
    and writes nothing; every slot no live row names is bit-identical."""
    x, dt, a, bm, cm, d, pool, z = _step_inputs(jax.random.PRNGKey(3), rows,
                                                1, 256, 8, 20)
    slots = jnp.asarray([3, 0, 5, 7, 0, 1, 2, 9, 10, 11, 13, 4, 6, 8, 12,
                         0][:rows])
    live = (slots != 0)[:, None]
    y, got = ssm._selective_step_pallas(
        x[:, 0], dt[:, 0], a.T, bm[:, 0], cm[:, 0], d, pool, slots, z[:, 0],
        interpret=True)
    want_y, want = ssm.selective_step_paged(x, dt, a, bm, cm, d, pool, slots,
                                            live, z)
    np.testing.assert_allclose(y, want_y[:, 0], atol=1e-5)
    named = np.asarray(slots[slots != 0])
    np.testing.assert_allclose(got[named], want[named], atol=1e-6)
    untouched = np.setdiff1d(np.arange(20), named)
    np.testing.assert_array_equal(got[untouched], pool[untouched])
    assert not np.asarray(y)[np.asarray(slots) == 0].any()


def test_a_dead_row_and_a_finished_row_move_no_state():
    """Off the kernel's path, a window of two: a token that is not live
    leaves its stream's state where it was, and its neighbours' too."""
    x, dt, a, bm, cm, d, pool, z = _step_inputs(jax.random.PRNGKey(4), 3, 2,
                                                64, 8, 5)
    slots = jnp.asarray([2, 4, 1])
    live = jnp.asarray([[True, True], [True, False], [False, False]])
    y, got = ssm.selective_step_paged(x, dt, a, bm, cm, d, pool, slots, live,
                                      z)
    np.testing.assert_array_equal(got[1], pool[1])
    np.testing.assert_array_equal(got[jnp.asarray([0, 3])],
                                  pool[jnp.asarray([0, 3])])
    lengths = jnp.asarray([2, 1, 0])
    want_y, want_s = _recurrence(x, dt, a, bm, cm, d, pool[slots], lengths, z)
    np.testing.assert_allclose(got[slots], want_s, atol=1e-5)
    np.testing.assert_allclose(y, want_y, atol=1e-4)


# ------------------------------------- the convolution's tail in the pool
RANKS = {"flat": lambda n, width, c: (n, width * c),       # Jamba's slots
         "slot-major": lambda n, width, c: (n, width, c)}  # Kimi's


def _tail_inputs(key, b, w, c, kk, n_slots, rank):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (b, w, c)),
            jax.random.normal(ks[1], (kk, c)),
            jax.random.normal(ks[2], RANKS[rank](n_slots, kk - 1, c)))


def _gather_conv_scatter(raw, w, pool, slots, live):
    """The five ops ``decode_window_paged`` ran before the pool entry: the
    oracle."""
    width = w.shape[0] - 1
    before = pool[slots].reshape(raw.shape[0], width, raw.shape[2])
    seen = jnp.concatenate([before, raw], axis=1)
    tail = kda.conv_tail(seen, width + jnp.sum(live, axis=1), width)
    return (kda.causal_conv(raw, w, before),
            pool.at[slots].set(tail.reshape((-1,) + pool.shape[1:])))


@pytest.mark.parametrize("rows", [11, 16], ids=["11-rows", "16-rows"])
@pytest.mark.parametrize("rank", sorted(RANKS))
def test_the_tail_kernel_is_the_five_ops_on_the_named_slots(rank, rows):
    """Live rows step their own slot in place, in a pool of either rank; a
    dead row (slot 0) reads 0 and writes nothing; every slot no live row
    names, the trash slot among them, is bit-identical."""
    raw, w, pool = _tail_inputs(jax.random.PRNGKey(7), rows, 1, 256, 4, 20,
                                rank)
    slots = jnp.asarray([3, 0, 5, 7, 0, 1, 2, 9, 10, 11, 13, 4, 6, 8, 12,
                         0][:rows])
    y, got = kda._conv_step_pallas(raw[:, 0], w, pool, slots, interpret=True)
    want_y, want = _gather_conv_scatter(raw, w, pool, slots,
                                       (slots != 0)[:, None])
    live = np.asarray(slots) != 0
    np.testing.assert_allclose(y[live], want_y[live, 0], atol=1e-6)
    named = np.asarray(slots)[live]
    np.testing.assert_allclose(got[named], want[named], atol=1e-6)
    assert float(jnp.abs(got[named] - pool[named]).max()) > 0.01
    untouched = np.setdiff1d(np.arange(20), named)
    np.testing.assert_array_equal(got[untouched], pool[untouched])
    assert not np.asarray(y)[~live].any()


def _on_the_chips_branch(monkeypatch):
    """Steer the pool entry to the TPU's branch, the kernel itself through
    the interpreter -> the slots each call was given."""
    calls, kernel = [], kda._conv_step_pallas

    def spy(raw, w, pool, slots):
        calls.append(np.asarray(slots).tolist())
        return kernel(raw, w, pool, slots, interpret=True)

    monkeypatch.setattr(kda, "_conv_step_pallas", spy)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return calls


@pytest.mark.parametrize("rank", sorted(RANKS))
def test_a_dead_row_and_a_finished_row_move_no_tail(monkeypatch, rank):
    """On the kernel's path and off it (a window of two): a token that is
    not live leaves its stream's tail where it was, and the trash slot and
    its neighbours' too."""
    calls = _on_the_chips_branch(monkeypatch)
    slots = jnp.asarray([2, 4, 0, 1])
    for w, live in ((1, [[True], [False], [False], [True]]),
                    (2, [[True, True], [True, False], [False, False],
                         [False, False]])):
        raw, wt, pool = _tail_inputs(jax.random.PRNGKey(w), 4, w, 128, 4, 6,
                                     rank)
        live = jnp.asarray(live)
        y, got = kda.conv_step_paged(raw, wt, pool, slots, live)
        want_y, want = _gather_conv_scatter(raw, wt, pool, slots, live)
        rows = np.asarray(live[:, 0])
        np.testing.assert_allclose(y[rows], want_y[rows], atol=1e-6)
        np.testing.assert_allclose(got, want, atol=1e-6)
        still = [s for s, l in zip([2, 4, 0, 1], rows) if not l] + [3, 5]
        np.testing.assert_array_equal(got[jnp.asarray(still)],
                                      pool[jnp.asarray(still)])
    assert calls == [[2, 0, 0, 1]]      # the window of two is the XLA form's
    # after one live token of two, the tail is the old one moved up by one
    t = lambda a: np.asarray(a[4]).reshape(3, 128)
    np.testing.assert_array_equal(t(got)[:2], t(pool)[1:])
    np.testing.assert_array_equal(t(got)[2], raw[1, 0])


@pytest.mark.parametrize("rank", sorted(RANKS))
def test_128_steps_from_a_prefills_tail_are_the_whole_convolution(rank):
    """Rows of their own prompt lengths (one shorter than the tail): the
    prefill's tail in the slots, then 128 kernel steps a token at a time,
    against ``causal_conv`` over each row's whole sequence."""
    lens, slots = np.asarray([9, 2, 30]), jnp.asarray([2, 0, 1])
    x, w, pool = _tail_inputs(jax.random.PRNGKey(11), 3, 30 + 128, 128, 4,
                              4, rank)
    whole = kda.causal_conv(x, w)
    tail = kda.conv_tail(x, jnp.asarray(lens), 3)
    start = pool.at[slots].set(tail.reshape((-1,) + pool.shape[1:]))
    step = jax.jit(lambda raw, p: kda._conv_step_pallas(raw, w, p, slots,
                                                        interpret=True))
    got = start
    for i in range(128):
        y, got = step(x[np.arange(3), lens + i], got)
        for r in (0, 2):
            np.testing.assert_allclose(y[r], whole[r, lens[r] + i],
                                       atol=1e-5)
        assert not np.asarray(y[1]).any()
    np.testing.assert_array_equal(got[jnp.asarray([0, 3])],
                                  start[jnp.asarray([0, 3])])
    ends = kda.conv_tail(x, jnp.asarray(lens + 128), 3)
    np.testing.assert_array_equal(
        got[jnp.asarray([2, 1])].reshape(2, 3, 128), ends[jnp.asarray([0, 2])])


def test_the_tail_entry_takes_the_kernel_where_the_chip_would(monkeypatch):
    """One token a row and channels in whole lane tiles go to the kernel
    with the dead rows on slot 0; a window of two, odd widths and every
    other backend gather, convolve and scatter, bit for bit what
    ``decode_window_paged`` did before."""
    slots = jnp.asarray([5, 0, 2, 4], jnp.int32)
    for w, c in ((1, 128), (2, 128), (1, 96)):      # a CPU: the XLA form
        raw, wt, pool = _tail_inputs(jax.random.PRNGKey(c + w), 4, w, c, 4,
                                     6, "flat")
        live = jnp.asarray([[True] * w, [False] * w, [True] + [False] * (w - 1),
                            [True] * w])
        got = jax.jit(kda.conv_step_paged)(raw, wt, pool, slots, live)
        want = jax.jit(_gather_conv_scatter)(raw, wt, pool, slots, live)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)
    calls = _on_the_chips_branch(monkeypatch)
    raw, wt, pool = _tail_inputs(jax.random.PRNGKey(2), 4, 1, 128, 4, 6,
                                 "flat")
    live = jnp.asarray([[True], [False], [False], [True]])
    y, got = kda.conv_step_paged(raw, wt, pool, slots, live)
    assert calls == [[5, 0, 0, 4]]
    want_y, want = _gather_conv_scatter(raw, wt, pool, slots, live)
    np.testing.assert_allclose(y[jnp.asarray([0, 3])],
                               want_y[jnp.asarray([0, 3])], atol=1e-6)
    np.testing.assert_allclose(got, want, atol=1e-6)
    for w, c in ((1, 96), (2, 128)):    # stay off it, on a TPU too
        raw, wt, pool = _tail_inputs(jax.random.PRNGKey(3), 4, w, c, 4, 6,
                                     "flat")
        kda.conv_step_paged(raw, wt, pool, slots, jnp.ones((4, w), bool))
    assert len(calls) == 1


def _kernels_a_step_takes(monkeypatch, blk, window, backend):
    """The names of the in-place kernels one ``decode_window_paged`` of
    ``blk`` reaches on ``backend``, each through the interpreter."""
    taken = []

    def through(module, name):
        kernel = getattr(module, name)
        monkeypatch.setattr(module, name, lambda *a: (
            taken.append(name), kernel(*a, interpret=True))[1])

    through(kda, "_conv_step_pallas")
    through(kda, "_kda_decode_pallas")
    through(ssm, "_selective_step_pallas")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    params, _ = blk.initialize(jax.random.PRNGKey(0), None)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, window, blk.hidden_size))
    at = jnp.asarray([[4], [0], [2]]) + jnp.arange(window)[None, :]
    out, pool = blk.decode_window_paged(
        params, x, blk.init_pool(5), jnp.asarray([1, 0, 3]), at, 8,
        limits=jnp.asarray([9, -1, 9]))
    assert bool(jnp.isfinite(out).all())
    return sorted(taken)


@pytest.mark.parametrize("case,hidden,window,backend,want", [
    ("whole-tiles-one-token", 64, 1, "tpu",
     ["_conv_step_pallas", "_selective_step_pallas"]),
    ("a-window-of-two", 64, 2, "tpu", []),
    ("96-channels", 48, 1, "tpu", []),
    ("a-cpu", 64, 1, "cpu", [])])
def test_the_state_kernel_and_conv_step_are_taken_under_one_predicate(
        monkeypatch, case, hidden, window, backend, want):
    """A state-space layer's decode step visits a live row's slot in both
    pools or gathers and scatters both: the series that count the slots a
    step visits (``ssm_decode_states_*``) cannot count one without the
    other."""
    blk = HybridDecoderBlock(hidden_size=hidden, mixer="mamba", d_state=8,
                             dt_rank=8, expand=2, ffn_size=32)
    assert _kernels_a_step_takes(monkeypatch, blk, window, backend) == want


def test_a_reused_slot_starts_from_zeros_and_neighbours_keep_theirs(served):
    """A stream's answer does not depend on who held its slot before, on
    the rows beside it, or on a neighbour that ends early."""
    _, gen = served
    alone = gen.generate(PROMPTS[:1], max_new_tokens=8)[0]
    gen.generate([[9] * 30, [7] * 25, [8] * 5, [2] * 12], max_new_tokens=9)
    assert gen.generate(PROMPTS[:1], max_new_tokens=8)[0] == alone
    together = gen.generate(PROMPTS, max_new_tokens=8)
    assert together[0] == alone
    # the third row stops at its first token; the others go on as they would
    eos = together[2][0]
    early = gen.generate(PROMPTS, max_new_tokens=8, eos_id=eos)
    cut = lambda row: row[:row.index(eos) + 1] if eos in row else row
    assert early == [cut(r) for r in together] and len(early[2]) == 1
    states = [p["state"] for p in gen.pool.pools if "state" in p]
    # the trash slot of padded rows is no stream's, whatever it holds
    assert len(states) == 3 and all(s.shape[0] == 5 for s in states)
    ok, detail = gen.pool.conservation()
    assert ok, detail


def test_the_convolution_and_its_tail_exist_once():
    """Both state mixers read ops/kda.py's; ops/ssm.py copies neither."""
    assert not any(hasattr(ssm, n) for n in ("causal_conv", "conv_tail",
                                             "conv_step_paged"))
    import inspect

    src = inspect.getsource(HybridDecoderBlock._ssm_prefill)
    assert "kda.causal_conv" in src and "kda.conv_tail" in src
    # a mixer runs the convolution it is handed; the decode step hands both
    # state mixers the one pool entry
    for inputs in (HybridDecoderBlock._ssm_inputs,
                   HybridDecoderBlock._kda_inputs):
        assert "conv(raw, " in inspect.getsource(inputs).split('"""')[2]
    step = inspect.getsource(HybridDecoderBlock.decode_window_paged)
    assert step.count("kda.conv_step_paged(") == 1
    assert 'pool["conv"][' not in step and 'pool["conv"].at[' not in step
    gen_src = inspect.getsource(inspect.getmodule(Generator))
    assert "ops import kda" not in gen_src and "ops.kda" not in gen_src
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 6))
    w = jax.random.normal(jax.random.PRNGKey(6), (4, 6))
    whole = kda.causal_conv(x, w)
    tail = kda.conv_tail(x, jnp.asarray([6, 2]), 3)
    np.testing.assert_allclose(
        kda.causal_conv(x[:1, 6:], w, tail[:1]), whole[:1, 6:], atol=1e-6)
    assert not np.asarray(tail[1, 0]).any()        # before the row's start


# -------------------------------------------------------- grouped attention
@pytest.mark.parametrize("n_kv", [1, 2])
def test_grouped_paged_decode_is_the_dense_softmax(n_kv):
    """A window of 3 queries a head, 4 heads over ``n_kv`` K/V heads, over
    rows ``n_kv x [k | v]`` scattered through a page table."""
    b, h, w, dh, bs, t = 2, 4, 3, 16, 8, 21
    ks = jax.random.split(jax.random.PRNGKey(7 + n_kv), 3)
    q = jax.random.normal(ks[0], (b, h, w, dh))
    k = jax.random.normal(ks[1], (b, t, n_kv, dh))
    v = jax.random.normal(ks[2], (b, t, n_kv, dh))
    tables = jnp.asarray([[3, 1, 6], [2, 5, 4]])
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    slots = attn_ops.paged_slots(tables, pos, bs)
    rows = jnp.concatenate([k, v], -1).reshape(b * t, n_kv * 2 * dh)
    pool = jnp.zeros((7 * bs, n_kv * 2 * dh)).at[slots.reshape(-1)].set(rows)
    positions = jnp.asarray([[18, 19, 20], [9, 10, 11]])
    got = attn_ops.grouped_paged_attention(q, pool, tables, positions, bs,
                                           n_kv)
    kk, vv = (jnp.repeat(a, h // n_kv, axis=2) for a in (k, v))
    s = jnp.einsum("bhwd,bthd->bhwt", q, kk) / dh ** 0.5
    keep = jnp.arange(t)[None, None, None, :] <= positions[:, None, :, None]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    want = jnp.einsum("bhwt,bthd->bhwd", p, vv)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_a_gqa_block_resumes_a_prefill_from_its_paged_rows(served):
    _, gen = served
    gqa, p = gen.blocks[1], gen.net.params[2]
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 12, 64))
    mask = jnp.ones((2, 12))
    tables = jnp.asarray([[1, 2], [3, 4]])
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
    pool = gqa.init_pool(5 * 8)
    whole, _ = gqa.prefill_paged(p, x, pool, attn_ops.paged_slots(
        tables, pos, 8), mask=mask)
    first, pool = gqa.prefill_paged(p, x[:, :8], pool, attn_ops.paged_slots(
        tables, pos[:, :8], 8), mask=mask[:, :8])
    rest, _ = gqa.prefill_resume_paged(p, x[:, 8:], pool, tables, pos[:, 8:],
                                       8)
    np.testing.assert_allclose(jnp.concatenate([first, rest], 1), whole,
                               atol=1e-5)


@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefill_chunk=16), "prefill_chunk"),
    (dict(draft_net="net"), "speculative"),
], ids=["prefix-cache", "chunked-prefill", "draft"])
def test_a_recurrent_net_refuses_what_needs_a_state_snapshot(served, kw,
                                                            what):
    _, gen = served
    if "draft_net" in kw:
        kw = dict(draft_net=gen.net)
    with pytest.raises(ValueError, match=what):
        Generator(gen.net, max_length=96, block_size=8, **kw)


# ---------------------------------------------------------------- tied head
def test_the_tied_head_holds_one_array_and_is_n_e_transposed(served):
    _, gen = served
    head, emb = gen.net.params[-1], gen.net.params[0]
    assert set(head) == {"norm", "E"} and head["E"] is emb["word"]
    leaves = jax.tree_util.tree_leaves(gen.net.params)
    assert sum(a is emb["word"] for a in leaves) == 2     # one array, twice
    assert sum(a.shape == emb["word"].shape for a in leaves) == 2
    layer = NormedLogitsLayer(n_in=64, n_out=96, eps=1e-6, tied=True)
    assert set(layer.initialize(jax.random.PRNGKey(0), None)[0]) == {"norm"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 64))
    n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * head["norm"]
    np.testing.assert_allclose(gen.head._logits(head, x),
                               n @ emb["word"].T, atol=1e-5)
    # the program contracts E's own second axis: no transposed copy of it
    text = jax.jit(gen.head._logits).lower(head, x).as_text()
    assert "transpose" not in text


# ----------------------------------------------------------------- counters
def test_ssm_counters_follow_the_lengths_and_the_live_rows(served):
    """Host arithmetic, no device fetch: a prefill of 4 rows x 32 positions
    declares 128 positions a Mamba layer and holds the prompts' tokens (and
    one of the padding row); 3 streams in a bucket of 4 move 3 of 4 declared
    states a layer a step. The series reach ``/metrics``, their ratios
    ``pool_stats()``, and no ``kda`` series is made by this net."""
    from deeplearning4j_tpu.util import telemetry as tm

    _, gen = served
    tele = tm.get_telemetry()
    read = lambda stem: [tele.counter_total(
        f"serving.{stem}_{n}_total", model=gen.model_id)
        for n in ("live", "declared")]
    p0, d0 = read("ssm_prefill_positions"), read("ssm_decode_states")
    gen.generate(PROMPTS, max_new_tokens=5)            # 4 decode steps
    p1, d1 = read("ssm_prefill_positions"), read("ssm_decode_states")
    assert [b - a for a, b in zip(p0, p1)] == [3 * (7 + 20 + 3 + 1), 3 * 128]
    assert [b - a for a, b in zip(d0, d1)] == [4 * 3 * 3, 4 * 4 * 3]
    gen.generate(PROMPTS, max_new_tokens=1)            # no decode step
    assert read("ssm_decode_states") == d1
    stats = gen.pool_stats()
    (held, declared, moved, rows), = gen._walked.values()
    assert stats["ssm_prefill_position_share"] == round(held / declared, 4)
    assert stats["ssm_decode_state_share"] == round(moved / rows, 4)
    assert 0 < stats["ssm_prefill_position_share"] < 0.5
    assert not [k for k in stats if k.startswith("kda")]
    text = tele.prometheus_text()
    for stem in ("ssm_prefill_positions", "ssm_decode_states"):
        for n in ("live", "declared"):
            assert f"dl4j_serving_{stem}_{n}_total" in text
    assert read("kda_prefill_chunks") == [0, 0]
    assert read("kda_decode_states") == [0, 0]


def test_each_state_mixer_says_what_its_prefill_walks():
    """The protocol's ``state_walk``: the counters' name, the unit, its
    positions, whether the prefill's counts sum over the layers."""
    walks = {b.mixer: b.state_walk for b in (
        HybridDecoderBlock(mixer="kda"), HybridDecoderBlock(mixer="mamba"))}
    assert walks["kda"] == ("kda", "chunks", kda.CHUNK, False)
    assert walks["mamba"] == ("ssm", "positions", 1, True)
