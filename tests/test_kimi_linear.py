"""Kimi Linear on the serving path, at a small size on the CPU (hidden 64: a
leading dense layer, KDA, KDA+experts, MLA+experts, 8 experts top-2, seeded
weights): the program against the benchmark's plain reference, the three
forms of the KDA recurrence, absorbed against expanded latent attention, the
shares of all chips against the uncut layer, and the two kinds of cache in
one pool manager.

Everything runs in float32 at ``highest``, so the tolerances are those of
float32 sums taken in another order: 2e-4 on logits of size 0.5, 1e-4 on
one layer's outputs.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.manifest import module_from
from deeplearning4j_tpu.nn import moe
from deeplearning4j_tpu.nn.decoder import HybridDecoderBlock
from deeplearning4j_tpu.ops import attention as attn_ops
from deeplearning4j_tpu.ops import kda
from deeplearning4j_tpu.serving import ServingModel
from deeplearning4j_tpu.serving.generate import Generator
from deeplearning4j_tpu.serving.paged import BlockPool
from deeplearning4j_tpu.serving.resilience import PoolExhaustedError

CFG = dict(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=2,
    first_k_dense_replace=1, intermediate_size=128, moe_intermediate_size=32,
    num_experts=8, published_num_experts=8, expert_offset=0,
    num_experts_per_token=2, num_shared_experts=1,
    routed_scaling_factor=2.446, kv_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rms_norm_eps=1e-5, vocab_size=96,
    max_position_embeddings=96, param_dtype="float32", gate_low_rank=8,
    linear_attn_config=dict(full_attn_layers=[4], kda_layers=[1, 2, 3],
                            head_dim=16, num_heads=2,
                            short_conv_kernel_size=4))
PROMPTS = [[5, 6, 7, 8, 9, 10, 11], [3] * 20, [1, 2, 3]]


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def ref():
    return module_from("reference", "kimi_linear")


@pytest.fixture(scope="module")
def served(ref):
    """(weights, generator) of the small model, built as the benchmark
    builds it: the builder's net, the reference's weights."""
    builder = module_from("builders", "zoo.KimiLinear")
    w = ref.make_weights(3, CFG)
    net = builder.build(CFG)
    builder.load(net, w)
    gen = Generator(net, max_length=96, batch_buckets=(4,),
                    prefill_buckets=(32,), block_size=8)
    return w, gen


def _reference_logits(ref, w, prompts, served_tokens, dtype=None):
    new = len(served_tokens[0])
    toks = np.zeros((len(prompts), 48), np.int32)
    pos = np.zeros((len(prompts), new), np.int32)
    for i, (p, o) in enumerate(zip(prompts, served_tokens)):
        seq = list(p) + list(o[:-1])
        toks[i, :len(seq)] = seq
        pos[i] = len(p) - 1 + np.arange(new)
    return ref.logits_at(w, jnp.asarray(toks), jnp.asarray(pos), n_heads=2,
                         dtype=dtype)


# ------------------------------------------------- program against reference
def test_prefill_then_decode_matches_the_references_full_forward(ref, served):
    """Prefill and 11 decode steps through the paged path, ragged prompts
    and a padded row in one batch: the logits behind every served token are
    the reference's (full forward, no cache) to 2e-4. Every slot a stream
    wrote then holds a latent row in lanes 0-31 and exactly 0 in lanes
    32-127 of the stored width, as does the rest of the pool (the trash
    block takes the padding's rows, zero lanes and all)."""
    w, gen = served
    raw = gen._raw_params()
    tokens, lengths, b_real, lens = gen._prep(PROMPTS, 12)
    tables_list, addr, *_, held = gen._admit(lens, 12, 4)
    limits = jnp.asarray([l + 11 for l in lens] + [0], jnp.int32)
    logits, gen.pool.pools = gen._prefill_paged_jit(
        raw, gen.pool.pools, tokens, lengths, addr)
    got, out, pos = [logits], [], lengths
    for _ in range(11):
        cur = jnp.argmax(got[-1], -1).astype(jnp.int32)
        out.append(cur)
        logits, gen.pool.pools = gen._decode_paged_jit(
            raw, gen.pool.pools, addr, cur, pos, limits)
        got.append(logits)
        pos = pos + 1
    out.append(jnp.argmax(got[-1], -1).astype(jnp.int32))
    gen.pool.release(tables_list, held)
    rows = np.asarray(gen.pool.pools[3]["rows"])
    written = [tables_list[i][t // 8] * 8 + t % 8
               for i, n in enumerate(lens) for t in range(n + 11)]
    assert rows.shape[1] == 128 and len(set(written)) == sum(lens) + 33
    assert not rows[:, 32:].any()
    assert np.abs(rows[written, :32]).sum(axis=1).min() > 0
    got = np.stack([np.asarray(g) for g in got], 1)[:3]       # (3, 12, V)
    served_tokens = np.stack([np.asarray(o) for o in out], 1)[:3].tolist()
    want = np.asarray(_reference_logits(ref, w, PROMPTS, served_tokens))
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert gen.generate(PROMPTS, max_new_tokens=12) == served_tokens


def test_the_fp8_control_is_seen(ref, served):
    w, gen = served
    out = gen.generate(PROMPTS, max_new_tokens=12)
    lg = _reference_logits(ref, w, PROMPTS, out)
    low = _reference_logits(ref, w, PROMPTS, out,
                            dtype=jnp.dtype("float8_e4m3fn"))
    gap = lambda pick: float(jnp.max(jnp.max(lg, -1) - jnp.take_along_axis(
        lg, pick[..., None], -1)[..., 0]))
    assert gap(jnp.asarray(out)) == 0.0
    assert gap(jnp.argmax(low, -1)) > 0.01


def test_served_through_the_model_server_objects(served):
    """ServingModel, as chipbench/serve.py constructs it, serves the net;
    cache types come from the net, and ``pools = None`` frees every cache."""
    _, gen = served
    model = ServingModel(gen.net, "kimi", kind="generate", paged=True,
                         block_size=8, max_length=96,
                         bucketing="batch=4;seq=32")
    assert model.generator.generate(PROMPTS, max_new_tokens=4) == \
        [r[:4] for r in gen.generate(PROMPTS, max_new_tokens=12)]
    d = model.describe()
    assert d["kv_pool"]["recurrent"] is True
    assert d["kv_pool"]["bytes_by_kind"]["state"] > 0
    assert d["kv_pool"]["state_slots_total"] == 4
    kinds = {n: str(a.dtype) for p in model.generator.pool.pools
             for n, a in p.items()}
    assert kinds == {"state": "float32", "conv": "float32",
                     "rows": "float32", "moe": "int32"}
    model.generator.pool.pools = None


# ----------------------------------------------------------------------- KDA
def _kda_inputs(key, b, t, h, dk, dv, strong):
    ks = jax.random.split(key, 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jnp.exp(jax.random.normal(ks[3], (b, t, h, dk))
                 * (2.0 if strong else 0.5) - (0.0 if strong else 3.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, dk, dv))


# the chunked prefill three ways: as the serving path calls it at the small
# model's 16 x 8 heads (off the TPU that is the XLA form), and at the 128-wide
# heads the kernel takes, the XLA form beside the kernel under the interpreter
FORMS = {
    "served-16x8": (kda.kda_chunked, 16, 8),
    "xla-128": (kda._kda_chunked_xla, 128, 128),
    "kernel-128": (functools.partial(kda._kda_chunked_pallas, interpret=True),
                   128, 128),
}


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("t,strong", [(64, False), (150, True), (7, False),
                                      (130, False)])
def test_chunked_kda_is_the_recurrence(form, t, strong):
    """Whole chunks, a ragged tail, less than a sub-block, and decays so
    strong (exp(-50) a step) that a factorised decay would overflow."""
    chunked, dk, dv = FORMS[form]
    q, k, v, g, beta, s0 = _kda_inputs(jax.random.PRNGKey(t), 2, t, 3, dk, dv,
                                       strong)
    o1, s1 = kda.kda_recurrent(q, k, v, g, beta, s0)
    o2, s2 = jax.jit(chunked)(q, k, v, g, beta, s0)
    assert bool(jnp.isfinite(o2).all())
    np.testing.assert_allclose(o2, o1, atol=2e-5)
    np.testing.assert_allclose(s2, s1, atol=2e-5)


@pytest.mark.parametrize("form", FORMS)
def test_padded_tokens_leave_a_state_alone(form):
    """Rows of different lengths in one call (none, less than a sub-block,
    an end inside a chunk, whole chunks, the full window): the final state
    is the state at the row's length, the row of length 0 gets back the
    state it gave, bit for bit, and o is the recurrence's up to the end of
    the chunk a length ends in and exactly 0 in every chunk behind it."""
    chunked, dk, dv = FORMS[form]
    t, lens = 192, [0, 7, 100, 128, 192]
    q, k, v, g, beta, s0 = _kda_inputs(jax.random.PRNGKey(1), len(lens), t, 2,
                                       dk, dv, False)
    lengths = jnp.asarray(lens)
    o, s = jax.jit(chunked)(q, k, v, g, beta, s0, lengths)
    assert bool(jnp.isfinite(o).all())
    live = (jnp.arange(t)[None] < lengths[:, None]).astype(jnp.float32)
    want_o, _ = kda.kda_recurrent(q, k, v, g * live[..., None, None],
                                  beta * live[..., None], s0)
    for i, n in enumerate(lens):
        _, want = kda.kda_recurrent(q[i:i + 1, :n], k[i:i + 1, :n],
                                    v[i:i + 1, :n], g[i:i + 1, :n],
                                    beta[i:i + 1, :n], s0[i:i + 1])
        np.testing.assert_allclose(s[i], want[0], atol=2e-5)
        held = kda.live_chunks(n) * kda.CHUNK
        np.testing.assert_allclose(o[i, :held], want_o[i, :held], atol=2e-5)
        assert not np.asarray(o[i, held:]).any()
    np.testing.assert_array_equal(s[0], s0[0])


def test_the_kernels_bfloat16_passes_on_a_cpu():
    """The arithmetic the chip runs (one bfloat16 pass where XLA's default
    precision takes one on a TPU, three in the solve, the running sum in
    three exact pieces) under the interpreter: within bfloat16's 2**-8 of
    the largest value, where float32 operands give 2e-5."""
    q, k, v, g, beta, s0 = _kda_inputs(jax.random.PRNGKey(5), 2, 128, 2, 128,
                                       128, False)
    lengths = jnp.asarray([128, 70])
    live = (jnp.arange(128)[None] < lengths[:, None]).astype(jnp.float32)
    o1, s1 = kda.kda_recurrent(q, k, v, g * live[..., None, None],
                               beta * live[..., None], s0)
    o2, s2 = kda._kda_chunked_pallas(q, k, v, g, beta, s0, lengths,
                                     interpret=True, exact=False)
    for got, want in ((o2, o1), (s2, s1)):
        err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        assert 2e-5 < err < 2 ** -7, err


def test_kda_prefill_counters_follow_the_lengths(served):
    """Host arithmetic, no device fetch: a prefill of 4 rows x 32 positions
    declares 4 chunks of 64 a layer and holds one a row, padding row
    included; a longer bucket shows the skipped ones. A net without a
    ``"state"`` block reports no such share."""
    from deeplearning4j_tpu.util import telemetry as tm

    _, gen = served
    tele = tm.get_telemetry()
    read = lambda: [tele.counter_total(
        f"serving.kda_prefill_chunks_{n}_total", model=gen.model_id)
        for n in ("live", "declared")]
    l0, d0 = read()
    gen.generate(PROMPTS, max_new_tokens=2)
    l1, d1 = read()
    assert (l1 - l0, d1 - d0) == (4, 4)
    gen._count_state_prefill(16, 1024, [1024, 65, 64, 1])
    l2, d2 = read()
    assert (l2 - l1, d2 - d1) == (16 + 2 + 1 + 1 + 12, 16 * 16)
    share = gen.pool_stats()["kda_prefill_chunk_share"]
    (held, declared, _, _), = gen._walked.values()
    assert share == round(held / declared, 4)
    assert "dl4j_serving_kda_prefill_chunks_live_total" \
        in tele.prometheus_text()
    from deeplearning4j_tpu.zoo import Bert
    bert = Generator(Bert.tiny(causal=True, task="mlm", vocab_size=32,
                               max_length=32).init(), block_size=8)
    assert "kda_prefill_chunk_share" not in bert.pool_stats()


# --------------------------------------------- the decode step in the pool
def _decode_inputs(key, b, w, h, dk, dv, n_slots):
    q, k, v, g, beta, _ = _kda_inputs(key, b, w, h, dk, dv, False)
    pool = jax.random.normal(jax.random.fold_in(key, 7), (n_slots, h, dk, dv))
    return q, k, v, g, beta, pool


def _gather_step_scatter(q, k, v, g, beta, pool, slots, live):
    """The decode step as it stood before the pool entry: the oracle."""
    m = live.astype(jnp.float32)
    o, s = kda.kda_recurrent(q, k, v, g * m[..., None, None],
                             beta * m[..., None], pool[slots])
    return o, pool.at[slots].set(s)


# slots (B,), rows past their limit (live False with a slot of their own)
DECODE_ROWS = {
    "every-row-live": ([3, 1, 6, 4], [True] * 4),
    "dead-rows-share-slot-0": ([3, 0, 6, 0, 0, 2], [True, False, True,
                                                    False, False, True]),
    "permuted-slots": ([6, 2, 5, 1, 3], [True] * 5),
    "one-live-row": ([0, 0, 4, 0], [False, False, True, False]),
    "a-finished-row-keeps-its-slot": ([2, 5, 1], [True, False, True]),
    "a-dead-row-first-and-between": ([0, 3, 5, 6], [False, True, False,
                                                    True]),
}


@pytest.mark.parametrize("heads", [2, 8])
@pytest.mark.parametrize("rows", DECODE_ROWS)
def test_the_decode_kernel_is_the_step_on_the_named_slots(rows, heads):
    """The kernel under the interpreter at 128-wide heads, two head groups
    a row: o of the live rows and their slots are ``kda_step`` on the
    gathered rows to 1e-5 of the largest value; every slot the step does not
    name, every slot a dead row names and the trash slot are bit-identical;
    a dead row's o is 0."""
    slots, live = DECODE_ROWS[rows]
    q, k, v, g, beta, pool = _decode_inputs(
        jax.random.PRNGKey(len(slots)), len(slots), 1, 2 * heads, 128, 128, 7)
    slots, live = jnp.asarray(slots, jnp.int32), np.asarray(live)
    one = lambda a: a[:, 0]
    o, new = kda._kda_decode_pallas(
        one(q), one(k), one(v), one(g), one(beta), pool,
        jnp.where(live, slots, 0), interpret=True, head_group=heads)
    want_o, want_s = kda.kda_step(one(q), one(k), one(v), one(g), one(beta),
                                  pool[slots])
    named = np.asarray(slots)[live]
    tol = lambda a: 1e-5 * float(jnp.abs(a).max())
    np.testing.assert_allclose(o[live], want_o[live], atol=tol(want_o))
    np.testing.assert_allclose(new[named], want_s[live], atol=tol(want_s))
    assert float(jnp.abs(new[named] - pool[named]).max()) > 0.01
    assert not np.asarray(o[~live]).any()
    rest = np.asarray([i for i in range(7) if i not in set(named.tolist())])
    np.testing.assert_array_equal(new[rest], pool[rest])


@pytest.mark.parametrize("case,w,dk,dv", [("narrow-heads", 1, 16, 8),
                                          ("a-window-of-two", 2, 128, 128),
                                          ("one-token-on-a-cpu", 1, 128, 128)])
def test_the_pool_entry_off_the_kernels_path_is_gather_step_scatter(case, w,
                                                                    dk, dv):
    """Narrow heads, a window of two tokens, and any shape off the TPU take
    the XLA form, bit for bit what ``decode_window_paged`` did before."""
    q, k, v, g, beta, pool = _decode_inputs(jax.random.PRNGKey(w), 4, w, 2,
                                            dk, dv, 6)
    slots = jnp.asarray([5, 0, 2, 4], jnp.int32)
    live = jnp.asarray([[True] * w, [False] * w, [True] + [False] * (w - 1),
                        [True] * w])
    got = jax.jit(kda.kda_step_paged)(q, k, v, g, beta, pool, slots, live)
    want = jax.jit(_gather_step_scatter)(q, k, v, g, beta, pool, slots, live)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(got[1][jnp.asarray([1, 3])],
                                  pool[jnp.asarray([1, 3])])


def test_the_pool_entry_takes_the_kernel_where_the_chip_would(monkeypatch):
    """Steered to the TPU's branch (the kernel itself through the
    interpreter): one token a row at 128-wide heads goes to the kernel with
    the dead rows on slot 0, and the result is the XLA form's."""
    calls = []
    kernel = kda._kda_decode_pallas

    def spy(*a, **kw):
        calls.append(np.asarray(a[6]).tolist())
        return kernel(*a, interpret=True, **kw)

    monkeypatch.setattr(kda, "_kda_decode_pallas", spy)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, k, v, g, beta, pool = _decode_inputs(jax.random.PRNGKey(2), 4, 1, 2,
                                            128, 128, 6)
    slots = jnp.asarray([5, 0, 2, 4], jnp.int32)
    live = jnp.asarray([[True], [False], [False], [True]])
    o, new = kda.kda_step_paged(q, k, v, g, beta, pool, slots, live)
    assert calls == [[5, 0, 0, 4]]
    want_o, want = _gather_step_scatter(q, k, v, g, beta, pool, slots, live)
    keep = np.asarray(live[:, 0])
    np.testing.assert_allclose(o[keep], want_o[keep], atol=1e-5)
    np.testing.assert_allclose(new[1:], want[1:], atol=1e-5)
    np.testing.assert_array_equal(new[jnp.asarray([0, 1, 2, 3])],
                                  pool[jnp.asarray([0, 1, 2, 3])])
    # narrow heads and a wider window stay off it, on a TPU too
    for w, d in ((1, 16), (2, 128)):
        a = _decode_inputs(jax.random.PRNGKey(3), 4, w, 2, d, d, 6)
        kda.kda_step_paged(*a, slots, jnp.ones((4, w), bool))
    assert len(calls) == 1


# ---------------------------------- the convolutions' tail in the pool
def _tail_inputs(key, b, w, c, n_slots):
    """Projections (B, W, C), the q | k | v convolutions' weights (4, C) and
    a tail pool as a KDA block's ``init_pool`` makes it, (slots, 3, C)."""
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (b, w, c)),
            jax.random.normal(ks[1], (4, c)),
            jax.random.normal(ks[2], (n_slots, 3, c)))


def _gather_conv_scatter(raw, w, pool, slots, live):
    """The five ops ``decode_window_paged`` ran before the pool entry: the
    oracle."""
    before = pool[slots]
    seen = jnp.concatenate([before, raw], axis=1)
    tail = kda.conv_tail(seen, 3 + jnp.sum(live, axis=1), 3)
    return kda.causal_conv(raw, w, before), pool.at[slots].set(tail)


@pytest.mark.parametrize("rows", DECODE_ROWS)
def test_the_tail_kernel_is_the_five_ops_on_the_named_slots(rows):
    """The kernel under the interpreter on a KDA block's pool: y of the live
    rows and their slots are gather, ``causal_conv``, ``conv_tail``,
    scatter to 1e-6; every slot the step does not name, every slot a dead
    row names and the trash slot are bit-identical; a dead row's y is 0."""
    slots, live = DECODE_ROWS[rows]
    raw, w, pool = _tail_inputs(jax.random.PRNGKey(len(slots)), len(slots),
                                1, 3 * 128, 7)
    slots, live = jnp.asarray(slots, jnp.int32), np.asarray(live)
    y, new = kda._conv_step_pallas(raw[:, 0], w, pool,
                                   jnp.where(live, slots, 0), interpret=True)
    want_y, want = _gather_conv_scatter(raw, w, pool, slots,
                                        jnp.asarray(live)[:, None])
    named = np.asarray(slots)[live]
    np.testing.assert_allclose(y[live], want_y[live, 0], atol=1e-6)
    np.testing.assert_allclose(new[named], want[named], atol=1e-6)
    np.testing.assert_array_equal(new[named, :2], pool[named, 1:])
    assert not np.asarray(y[~live]).any()
    rest = np.asarray([i for i in range(7) if i not in set(named.tolist())])
    np.testing.assert_array_equal(new[rest], pool[rest])


def test_128_steps_from_a_prefills_tail_are_the_whole_convolution():
    """Rows of their own prompt lengths (one shorter than the tail): the
    prefill's tail in the slots, then 128 kernel steps a token at a time,
    against ``causal_conv`` over each row's whole sequence."""
    lens, slots = np.asarray([9, 2, 30]), jnp.asarray([2, 0, 1])
    x, w, pool = _tail_inputs(jax.random.PRNGKey(11), 3, 30 + 128, 128, 4)
    whole = kda.causal_conv(x, w)
    start = pool.at[slots].set(kda.conv_tail(x, jnp.asarray(lens), 3))
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
    np.testing.assert_array_equal(got[jnp.asarray([2, 1])],
                                  ends[jnp.asarray([0, 2])])


@pytest.mark.parametrize("case,w,c", [("96-channels", 1, 96),
                                      ("a-window-of-two", 2, 384),
                                      ("one-token-on-a-cpu", 1, 384)])
def test_the_tail_entry_off_the_kernels_path_is_the_five_ops(case, w, c):
    """Odd widths, a window of two tokens, and any shape off the TPU take
    the XLA form, bit for bit what ``decode_window_paged`` did before; a
    finished row's tail and the slots nobody names stay as they were."""
    raw, wt, pool = _tail_inputs(jax.random.PRNGKey(w), 4, w, c, 6)
    slots = jnp.asarray([5, 0, 2, 4], jnp.int32)
    live = jnp.asarray([[True] * w, [False] * w, [False] * w,
                        [True] + [False] * (w - 1)])
    got = jax.jit(kda.conv_step_paged)(raw, wt, pool, slots, live)
    want = jax.jit(_gather_conv_scatter)(raw, wt, pool, slots, live)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(got[1][jnp.asarray([1, 2, 3])],
                                  pool[jnp.asarray([1, 2, 3])])


def test_the_tail_entry_takes_the_kernel_where_the_chip_would(monkeypatch):
    """Steered to the TPU's branch (the kernel itself through the
    interpreter): one token a row at whole lane tiles goes to the kernel
    with the dead rows on slot 0, and the result is the XLA form's."""
    calls = []
    kernel = kda._conv_step_pallas

    def spy(raw, w, pool, slots):
        calls.append(np.asarray(slots).tolist())
        return kernel(raw, w, pool, slots, interpret=True)

    monkeypatch.setattr(kda, "_conv_step_pallas", spy)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    raw, wt, pool = _tail_inputs(jax.random.PRNGKey(2), 4, 1, 384, 6)
    slots = jnp.asarray([5, 0, 2, 4], jnp.int32)
    live = jnp.asarray([[True], [False], [False], [True]])
    y, new = kda.conv_step_paged(raw, wt, pool, slots, live)
    assert calls == [[5, 0, 0, 4]]
    want_y, want = _gather_conv_scatter(raw, wt, pool, slots, live)
    keep = np.asarray(live[:, 0])
    np.testing.assert_allclose(y[keep], want_y[keep], atol=1e-6)
    np.testing.assert_allclose(new, want, atol=1e-6)
    np.testing.assert_array_equal(new[jnp.asarray([0, 1, 2, 3])],
                                  pool[jnp.asarray([0, 1, 2, 3])])
    # odd widths and a wider window stay off it, on a TPU too
    for w, c in ((1, 96), (2, 384)):
        a = _tail_inputs(jax.random.PRNGKey(3), 4, w, c, 6)
        kda.conv_step_paged(*a, slots, jnp.ones((4, w), bool))
    assert len(calls) == 1


@pytest.mark.parametrize("case,head_dim,window,backend,want", [
    ("whole-tiles-one-token", 128, 1, "tpu",
     ["_conv_step_pallas", "_kda_decode_pallas"]),
    ("a-window-of-two", 128, 2, "tpu", []),
    ("heads-of-16", 16, 1, "tpu", []),
    ("a-cpu", 128, 1, "cpu", [])])
def test_the_state_kernel_and_conv_step_are_taken_under_one_predicate(
        monkeypatch, case, head_dim, window, backend, want):
    """A KDA layer's decode step visits a live row's slot in both pools or
    gathers and scatters both: the series that count the slots a step
    visits (``kda_decode_states_*``) cannot count one without the other."""
    taken = []

    def through(name):
        kernel = getattr(kda, name)
        monkeypatch.setattr(kda, name, lambda *a: (
            taken.append(name), kernel(*a, interpret=True))[1])

    through("_conv_step_pallas")
    through("_kda_decode_pallas")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    blk = HybridDecoderBlock(hidden_size=32, mixer="kda", n_heads=1,
                             head_dim=head_dim, gate_rank=8, ffn_size=32)
    params, _ = blk.initialize(jax.random.PRNGKey(0), None)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, window, 32))
    at = jnp.asarray([[4], [0], [2]]) + jnp.arange(window)[None, :]
    out, pool = blk.decode_window_paged(
        params, x, blk.init_pool(5), jnp.asarray([1, 0, 3]), at, 8,
        limits=jnp.asarray([9, -1, 9]))
    assert bool(jnp.isfinite(out).all()) and sorted(taken) == want


def test_kda_decode_counters_follow_the_live_rows(served):
    """Host arithmetic, no device fetch: a batch of three streams in a
    bucket of four moves 3 of 4 declared states a KDA layer a decode step;
    both series reach ``/metrics`` and their ratio ``pool_stats()``; a net
    without a ``"state"`` block reports neither."""
    from deeplearning4j_tpu.util import telemetry as tm

    _, gen = served
    tele = tm.get_telemetry()
    read = lambda: [tele.counter_total(
        f"serving.kda_decode_states_{n}_total", model=gen.model_id)
        for n in ("live", "declared")]
    kda_layers = sum(b.mixer == "kda" for b in gen.blocks)
    assert kda_layers == 3
    l0, d0 = read()
    gen.generate(PROMPTS, max_new_tokens=5)            # 4 decode steps
    l1, d1 = read()
    assert (l1 - l0, d1 - d0) == (4 * 3 * kda_layers, 4 * 4 * kda_layers)
    gen.generate(PROMPTS[:1], max_new_tokens=2)        # 1 step, 1 live row
    l2, d2 = read()
    assert (l2 - l1, d2 - d1) == (kda_layers, 4 * kda_layers)
    gen.generate(PROMPTS, max_new_tokens=1)            # no decode step
    assert read() == [l2, d2]
    share = gen.pool_stats()["kda_decode_state_share"]
    (_, _, moved, rows), = gen._walked.values()
    assert share == round(moved / rows, 4)
    assert 0.25 < share <= 0.75
    text = tele.prometheus_text()
    for n in ("live", "declared"):
        assert f"dl4j_serving_kda_decode_states_{n}_total" in text
    from deeplearning4j_tpu.zoo import Bert
    bert = Generator(Bert.tiny(causal=True, task="mlm", vocab_size=32,
                               max_length=32).init(), block_size=8,
                     model_id="bert-no-kda")
    bert.generate([[1, 2, 3]], max_new_tokens=3)
    assert "kda_decode_state_share" not in bert.pool_stats()
    assert tele.counter_total("serving.kda_decode_states_live_total",
                              model="bert-no-kda") == 0


def test_conv_tail_of_ragged_rows():
    x = jnp.arange(2 * 6 * 1, dtype=jnp.float32).reshape(2, 6, 1) + 1
    tail = kda.conv_tail(x, jnp.asarray([6, 2]), 3)
    assert tail[0, :, 0].tolist() == [4.0, 5.0, 6.0]
    assert tail[1, :, 0].tolist() == [0.0, 7.0, 8.0]


# ----------------------------------------------------------------------- MLA
def test_absorbed_latent_decode_is_the_expanded_form():
    """One MLA block: a window of 3 tokens decoded in absorbed form over
    paged latent rows against the expanded causal attention over the whole
    sequence."""
    blk = HybridDecoderBlock(hidden_size=64, mixer="mla", ffn="dense",
                             n_heads=2, kv_lora_rank=24, qk_nope_dim=16,
                             qk_rope_dim=8, v_head_dim=16, ffn_size=32)
    p, _ = blk.initialize(jax.random.PRNGKey(0), None)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 64))
    full, _ = blk.apply(p, {}, x)
    bs, lens = 4, jnp.asarray([17, 9])
    pool = blk.init_pool(16 * bs)
    tables = jnp.asarray(np.arange(1, 13).reshape(2, 6), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(20), (2, 20))
    mask = (pos < lens[:, None]).astype(jnp.float32)
    _, pool = blk.prefill_paged(p, x, pool,
                                attn_ops.paged_slots(tables, pos, bs),
                                mask=mask)
    win = lens[:, None] + jnp.arange(3)[None]
    x_w = jnp.take_along_axis(x, win[..., None], axis=1)
    got, _ = blk.decode_window_paged(p, x_w, pool, tables, win, bs)
    want = jnp.take_along_axis(full, win[..., None], axis=1)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_this_configurations_latent_layers_stay_unrotated_and_full_rank(
        served):
    """Rotation and the query rank are a configuration's (nn/decoder.py):
    Kimi's blocks keep neither, so their parameters and programs are what
    they were; the same block with both set has other leaves."""
    import dataclasses

    _, gen = served
    mla = [b for b in gen.blocks if b.mixer == "mla"]
    assert mla and all(not b.rope and b.q_lora_rank == 0 for b in mla)
    p, _ = mla[0].initialize(jax.random.PRNGKey(0), None)
    assert "Wq" in p and not {"Wdq", "q_norm", "Wuq"} & set(p)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 64))
    pos = jnp.arange(6)[None]
    h = jnp.ones((1, 6, 64))
    assert mla[0]._rope(x, pos) is x                    # a trace-time branch
    turned = dataclasses.replace(mla[0], rope=True, q_lora_rank=8)
    q, _ = turned.initialize(jax.random.PRNGKey(0), None)
    assert {"Wdq", "q_norm", "Wuq"} <= set(q) and "Wq" not in q
    rows = lambda blk, prm: blk._mla_rows(prm, h, pos)
    assert float(jnp.abs(rows(mla[0], p)[0, 0] - rows(mla[0], p)[0, 5]
                         ).max()) == 0.0                # no positions seen
    assert float(jnp.abs(rows(turned, q)[0, 0] - rows(turned, q)[0, 5]
                         ).max()) > 1e-3


# ------------------------------------------------------------------- experts
@pytest.mark.parametrize("pairs,held,routed,rows", [
    (128, 16, 256, 128), (131072, 16, 256, 16384), (128, 64, 64, 128),
    (131072, 64, 64, 131072), (4096, 64, 64, 4096), (1024, 8, 64, 256)])
def test_pass_rows_is_twice_the_fair_share_at_most_every_pick(pairs, held,
                                                              routed, rows):
    """A share of the experts gets passes of twice its fair share; with every
    expert held one pass takes every pick."""
    assert moe._pass_rows(pairs, held, routed) == rows


def test_the_shares_of_all_chips_sum_to_the_uncut_layer(ref):
    """Four chips of 2 experts each, the shared expert counted once: their
    partial results add up to the reference's uncut layer (8 experts held),
    and each share is the reference's own share."""
    cfg = dict(CFG, num_hidden_layers=2,
               linear_attn_config=dict(CFG["linear_attn_config"],
                                       full_attn_layers=[2], kda_layers=[1]))
    w = ref.make_weights(7, cfg)
    p = w["layers"][1]                                  # MLA + 8 experts
    d = ref._dims(cfg)
    h = jax.random.normal(jax.random.PRNGKey(2), (40, 64))
    mm = lambda a, b: jnp.matmul(a, b, precision="highest")
    uncut = ref._ffn(p, d, h, mm)
    shared = ref._ffn({k: v for k, v in p.items() if k[0] == "S"}
                      | {"router": p["router"],
                         "router_bias": p["router_bias"]},
                      dict(d, held=0), h, mm)
    idx, wts = moe.route_sigmoid_topk(h, p["router"], p["router_bias"], 2,
                                      2.446)
    total = shared
    for off in range(0, 8, 2):
        part, stats = moe.grouped_experts(
            h, idx, wts, p["Egate"][off:off + 2], p["Eup"][off:off + 2],
            p["Edown"][off:off + 2], e_offset=off, n_experts=8)
        share = {k: (v[off:off + 2] if k[0] == "E" else v)
                 for k, v in p.items()}
        want = ref._ffn(share, dict(d, held=2, offset=off), h, mm) - shared
        np.testing.assert_allclose(part, want, atol=1e-5)
        assert int(stats[0]) == 80 and 0 < int(stats[2]) <= 2
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=1e-5)


def test_a_skewed_router_takes_more_passes_and_drops_nothing():
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    n, hs, f = 300, 32, 24
    x = jax.random.normal(ks[0], (n, hs))
    wg, wu = (jax.random.normal(k, (4, hs, f)) * 0.2 for k in ks[1:3])
    wd = jax.random.normal(ks[3], (4, f, hs)) * 0.2
    idx = jnp.tile(jnp.arange(3)[None], (n, 1))         # all picks held here
    w = jax.random.uniform(ks[4], (n, 3))
    assert moe._pass_rows(n * 3, 4, 16) < n * 3         # so: several passes
    live = jnp.arange(n) < 100
    y, stats = jax.jit(lambda: moe.grouped_experts(
        x, idx, w, wg, wu, wd, e_offset=0, n_experts=16, live=live))()
    want = sum(w[:, e:e + 1] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e]))
                                @ wd[e]) for e in range(3))
    np.testing.assert_allclose(y[:100], want[:100], atol=1e-4)
    assert float(jnp.abs(y[100:]).max()) == 0.0
    assert stats.tolist() == [300, 300, 3, 100]


# ---------------------------------------------------------- the pool manager
def test_state_slots_and_blocks_are_conserved(served):
    """After release, after growth, and after an exception in the decode
    loop: every block and every state slot is back."""
    _, gen = served
    pool = gen.pool
    free0 = (pool.free_blocks(), len(pool._free_states))
    gen.generate(PROMPTS, max_new_tokens=5)
    assert (pool.free_blocks(), len(pool._free_states)) == free0
    assert pool.conservation()[0]

    boom = gen._decode_paged_jit
    gen._decode_paged_jit = lambda *a: (_ for _ in ()).throw(
        RuntimeError("boom"))
    with pytest.raises(RuntimeError, match="boom"):
        gen.generate(PROMPTS, max_new_tokens=5)
    gen._decode_paged_jit = boom
    assert (gen.pool.free_blocks(), len(gen.pool._free_states)) == free0
    assert gen.pool.conservation()[0]
    assert gen.generate(PROMPTS[:1], max_new_tokens=3)      # pools rebuilt


def test_an_auto_pool_grows_its_state_slots_too(served):
    _, big = served
    gen = Generator(big.net, max_length=96, batch_buckets="pow2",
                    prefill_buckets=(32,), block_size=8)
    gen.pool = BlockPool(gen.blocks, block_size=8, num_blocks=4,
                         max_length=96, state_slots=1)
    out = gen.generate(PROMPTS, max_new_tokens=4)
    assert out == [r[:4] for r in big.generate(PROMPTS, max_new_tokens=4)]
    assert gen.pool.num_state_slots >= 3 and gen.pool.conservation()[0]
    assert len(gen.pool._free_states) == gen.pool.num_state_slots


def test_a_pinned_pool_sheds_when_its_state_slots_run_out(served):
    _, big = served
    pool = BlockPool(big.blocks, block_size=8, num_blocks=64, max_length=96,
                     state_slots=2)
    tables = pool.reserve([1, 1])
    held = pool.reserve_states(2)
    with pytest.raises(PoolExhaustedError, match="state slots"):
        pool.reserve_states(1)
    pool.release(tables, held)
    with pytest.raises(ValueError, match="double-free"):
        pool.release([], held[:1])
    assert pool.conservation()[0]


def test_the_pools_figures_count_cache_and_not_the_router_counters(served):
    """The routed layers' counters ride in their layer's pool dict, (2, 5)
    int32: no size, type or block copy of the cache may take them for rows."""
    _, gen = served
    pool = gen.pool
    assert sum("moe" in p for p in pool.pools) == 3
    # one MLA layer: a latent row of kv_lora_rank + qk_rope_head_dim float32,
    # stored in whole 128-lane tiles (32 -> 128)
    assert pool.bytes_per_token() == 128 * 4
    # three KDA layers: (heads, 16, 16) float32 + 3 earlier inputs of q|k|v
    assert pool.bytes_per_stream_state() == 3 * (2 * 16 * 16 + 3 * 3 * 32) * 4
    stats = pool.stats()
    assert stats["dtype_by_kind"] == {"tokens": "float32", "state": "float32"}
    assert stats["bytes_by_kind"] == {
        "tokens": pool.num_blocks * 8 * 128 * 4, "state": 4 * 9600}
    # copy-on-write on a routed layer with rows (MLA + experts): the rows
    # of the block move, the counters stay as they were
    mla = pool.pools[3]
    assert mla["rows"].shape == (pool.num_slots, 128)
    rows = jnp.arange(mla["rows"].size, dtype=jnp.float32).reshape(
        mla["rows"].shape)
    moe = mla["moe"] + 7
    (out,) = gen._copy_block([dict(rows=rows, moe=moe)], 1, 2)
    assert out["moe"] is moe
    np.testing.assert_array_equal(out["rows"][16:24], rows[8:16])
    np.testing.assert_array_equal(out["rows"][:16], rows[:16])


# latent and rope widths -> the width a row is stored at
STORED = {
    "both-configurations-576": (512, 64, 640),
    "the-small-models-32": (24, 8, 128),
    "one-lane-over-a-tile": (121, 8, 256),
    "whole-tiles-already-256": (192, 64, 256),
    "one-whole-tile-128": (120, 8, 128),
}


@pytest.mark.parametrize("case", STORED)
def test_a_latent_row_is_stored_in_whole_lane_tiles(case):
    """The pool's minor width is the latent width rounded up to whole
    128-lane tiles, and the latent width itself where that is whole tiles
    already; ``Wdkv`` keeps the latent width; ``_mla_rows`` hands back the
    stored width with the extra lanes exactly zero."""
    rank, dr, stored = STORED[case]
    blk = HybridDecoderBlock(hidden_size=16, mixer="mla", ffn="dense",
                             n_heads=1, kv_lora_rank=rank, qk_nope_dim=8,
                             qk_rope_dim=dr, v_head_dim=8, ffn_size=8)
    pool = blk.init_pool(24)["rows"]
    assert pool.shape == (24, stored) and stored % 128 == 0
    assert (stored == rank + dr) == ((rank + dr) % 128 == 0)
    p, _ = blk.initialize(jax.random.PRNGKey(0), None)
    assert p["Wdkv"].shape == (16, rank + dr)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 16))
    rows = blk._mla_rows(p, h, jnp.zeros((2, 3), jnp.int32))
    assert rows.shape == (2, 3, stored)
    assert np.asarray(rows[..., :rank + dr]).all()
    assert not np.asarray(rows[..., rank + dr:]).any()


@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefill_chunk=8), "prefill_chunk"),
    (dict(draft_net="net"), "speculative"),
])
def test_a_recurrent_net_refuses_what_needs_a_state_snapshot(served, kw, what):
    _, gen = served
    if "draft_net" in kw:
        kw = dict(draft_net=gen.net)
    with pytest.raises(ValueError, match=what + ".*recurrent"):
        Generator(gen.net, max_length=96, batch_buckets=(4,), **kw)


def test_the_contiguous_engine_names_what_the_blocks_lack(served):
    _, gen = served
    with pytest.raises(ValueError, match="init_cache/prefill/decode_step"):
        Generator(gen.net, max_length=96, batch_buckets=(4,), paged=False)


def test_router_counters_reach_the_metrics_page(served):
    from deeplearning4j_tpu.util import telemetry as tm

    _, gen = served
    tele = tm.get_telemetry()
    before = {n: tele.counter_total(n) for n in (
        "serving.moe_picks_total", "serving.moe_picks_local_total",
        "serving.moe_decode_layer_steps_total")}
    gen.generate(PROMPTS, max_new_tokens=5)
    after = {n: tele.counter_total(n) for n in before}
    # 3 routed layers; prefill: 30 prompt tokens + the padded row's one;
    # decode: 3 live rows x 4 steps; 2 picks a token, all 8 experts held
    picks = 3 * 2 * (31 + 3 * 4)
    assert after["serving.moe_picks_total"] \
        - before["serving.moe_picks_total"] == picks
    assert after["serving.moe_picks_local_total"] \
        - before["serving.moe_picks_local_total"] == picks
    assert after["serving.moe_decode_layer_steps_total"] \
        - before["serving.moe_decode_layer_steps_total"] == 3 * 4
    text = tele.prometheus_text()
    for name in ("dl4j_serving_moe_experts_touched_total",
                 "dl4j_serving_moe_expert_load_max_total",
                 "dl4j_serving_state_slots_total",
                 "dl4j_serving_state_slots_free"):
        assert name in text
