"""GLM-4.7-Flash on the serving path, at a small size on the CPU (hidden 64:
a leading dense layer and two expert layers of 8 experts, top-2; 2 heads of
16 + 8 key dims with the 8 rotated, query rank 24; seeded weights): the
program against the benchmark's plain reference through prefill, decode,
resumed and chunked prefill and the verify window; the rotation where it
belongs (before the cache write, each row's own positions); the MTP module
against the reference's and as a self-draft whose output is the undrafted
one token for token; the counters.

Everything runs in float32 at ``highest``, so the tolerances are those of
float32 sums taken in another order: 2e-4 on logits of size 0.5, 1e-4 on
one layer's outputs. What must FAIL (no rotation, rotation after the cache
write, another row's positions) misses by 1e-2 or more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.manifest import module_from
from deeplearning4j_tpu.nn.decoder import (HybridDecoderBlock, rms_norm,
                                           rope)
from deeplearning4j_tpu.ops import attention as attn_ops
from deeplearning4j_tpu.serving import ServingModel
from deeplearning4j_tpu.serving.generate import Generator, SelfDraft
from deeplearning4j_tpu.util import telemetry as tm
from deeplearning4j_tpu.zoo import Glm4MoeLite

CFG = dict(
    hidden_size=64, num_hidden_layers=3, num_attention_heads=2,
    first_k_dense_replace=1, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=8, num_experts=8, n_shared_experts=1,
    num_experts_per_tok=2, routed_scaling_factor=1.8, q_lora_rank=24,
    kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=1e6, rms_norm_eps=1e-5, vocab_size=96,
    max_position_embeddings=96, param_dtype="float32",
    num_nextn_predict_layers=1)
PROMPTS = [[5, 6, 7, 8, 9, 10, 11], [3] * 20, [1, 2, 3]]
NEW = 12
GEN = dict(max_length=96, batch_buckets=(4,), prefill_buckets=(32,),
           block_size=8)


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def ref():
    return module_from("reference", "glm4_moe_lite")


@pytest.fixture(scope="module")
def built(ref):
    """(weights, net, builder) of the small model, built as the benchmark
    builds it: the builder's net, the reference's weights."""
    builder = module_from("builders", "zoo.Glm4MoeLite")
    w = ref.make_weights(3, CFG)
    # at hidden 64 the benchmark's N(0, 0.02) leaves scores near 0.01 and a
    # softmax that sees no positions: the query's and the key's matrices are
    # made 8 times larger, so scores spread by about 0.6 as at full width
    for p in w["layers"] + [w["mtp"]["block"]]:
        p.update(Wuq=p["Wuq"] * 8, Wdkv=p["Wdkv"] * 8)
    net = builder.build(CFG)
    builder.load(net, w)
    return w, net, builder


@pytest.fixture(scope="module")
def served(built):
    w, net, _ = built
    return w, Generator(net, **GEN)


@pytest.fixture(scope="module")
def plain(served):
    """What greedy decoding without a draft serves."""
    return served[1].generate(PROMPTS, max_new_tokens=NEW)


@pytest.fixture(scope="module")
def drafted(built):
    w, net, builder = built
    return w, Generator(net, self_draft=builder.self_draft(CFG, w), **GEN)


def _sequences(prompts, outs, width=48):
    """Prompt + served tokens but the last, padded: (tokens, the positions
    behind every served token)."""
    new = len(outs[0])
    toks = np.zeros((len(prompts), width), np.int32)
    pos = np.zeros((len(prompts), new), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        seq = list(p) + list(o[:-1])
        toks[i, :len(seq)] = seq
        pos[i] = len(p) - 1 + np.arange(new)
    return jnp.asarray(toks), jnp.asarray(pos)


def _decode(gen, steps, shift=0):
    """Prefill and ``steps`` decode steps through the paged programs ->
    (logits (3, steps + 1, V), served tokens). ``shift`` moves every decode
    position (the fault: another row's positions)."""
    raw = gen._raw_params()
    tokens, lengths, b_real, lens = gen._prep(PROMPTS, steps + 1)
    tables_list, addr, *_, held = gen._admit(lens, steps + 1, 4)
    limits = jnp.asarray([l + steps for l in lens] + [0], jnp.int32)
    logits, gen.pool.pools = gen._prefill_paged_jit(
        raw, gen.pool.pools, tokens, lengths, addr)
    got, out, pos = [logits], [], lengths + shift
    for _ in range(steps):
        cur = jnp.argmax(got[-1], -1).astype(jnp.int32)
        out.append(cur)
        logits, gen.pool.pools = gen._decode_paged_jit(
            raw, gen.pool.pools, addr, cur, pos, limits)
        got.append(logits)
        pos = pos + 1
    out.append(jnp.argmax(got[-1], -1).astype(jnp.int32))
    gen.pool.release(tables_list, held)
    return (np.stack([np.asarray(g) for g in got], 1)[:3],
            np.stack([np.asarray(o) for o in out], 1)[:3].tolist())


# ------------------------------------------------- program against reference
def test_prefill_then_decode_matches_the_references_full_forward(ref, served,
                                                                 plain):
    """Prefill and 11 decode steps through the paged path, prompts of 7, 20
    and 3 tokens and a padded row in one batch: the logits behind every
    served token are the reference's (full forward, no cache) to 2e-4."""
    w, gen = served
    got, out = _decode(gen, NEW - 1)
    want = np.asarray(ref.logits_at(w, *_sequences(PROMPTS, out), n_heads=2))
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert out == plain


def test_another_rows_positions_fail(ref, served):
    """Rows of unequal length decode at their OWN positions: every row one
    position late (what a row padded on the left would be given) misses the
    reference by far more than the tolerance."""
    w, gen = served
    got, out = _decode(gen, 3, shift=1)
    want = np.asarray(ref.logits_at(w, *_sequences(PROMPTS, out), n_heads=2))
    assert np.abs(got[:, 1:] - want[:, 1:]).max() > 1e-2
    np.testing.assert_allclose(got[:, 0], want[:, 0], atol=2e-4)


def test_the_fp8_control_is_seen(ref, served, plain):
    w, _ = served
    toks, pos = _sequences(PROMPTS, plain)
    lg = ref.logits_at(w, toks, pos, n_heads=2)
    low = ref.logits_at(w, toks, pos, n_heads=2,
                        dtype=jnp.dtype("float8_e4m3fn"))
    gap = lambda pick: float(jnp.max(jnp.max(lg, -1) - jnp.take_along_axis(
        lg, pick[..., None], -1)[..., 0]))
    assert gap(jnp.asarray(plain)) == 0.0
    assert gap(jnp.argmax(low, -1)) > 0.01


def test_served_through_the_model_server_objects(built, plain):
    """ServingModel, as chipbench/serve.py constructs it, serves the net; a
    net of latent layers only keeps token rows and no stream state."""
    _, net, _ = built
    model = ServingModel(net, "glm", kind="generate", paged=True,
                         block_size=8, max_length=96,
                         bucketing="batch=4;seq=32")
    assert model.generator.generate(PROMPTS, max_new_tokens=4) == \
        [r[:4] for r in plain]
    d = model.describe()
    assert d["kv_pool"]["recurrent"] is False
    assert d["kv_pool"]["self_draft"] is False
    assert d["kv_pool"]["bytes_by_kind"]["state"] == 0
    kinds = {n: str(a.dtype) for p in model.generator.pool.pools
             for n, a in p.items()}
    assert kinds == {"rows": "float32", "moe": "int32"}
    model.generator.pool.pools = None


def test_the_zoo_model_is_the_builders(built):
    _, net, _ = built
    zoo = Glm4MoeLite.tiny(n_local_experts=8).network()
    assert [dataclasses.asdict(a) for a in zoo.layers] == \
        [dataclasses.asdict(b) for b in net.layers]
    blocks = Glm4MoeLite().network().layers[1:-1]
    assert len(blocks) == 47 and blocks[0].ffn == "dense"
    b = blocks[1]
    assert (b.n_heads, b.q_lora_rank, b.kv_lora_rank, b.qk_nope_dim,
            b.qk_rope_dim, b.v_head_dim, b.rope, b.rope_theta) == \
        (20, 768, 512, 192, 64, 256, True, 1e6)
    assert (b.n_experts, b._held, b.top_k, b.routed_scale, b.ffn_size,
            b.shared_size) == (64, 64, 4, 1.8, 1536, 1536)


# ------------------------------------------ resumed prefill, the prefix cache
@pytest.mark.parametrize("kw", [dict(prefill_chunk=8),
                                dict(prefix_cache=True)],
                         ids=["chunked", "prefix-cache"])
def test_resumed_prefill_gives_the_whole_prefills_rows(ref, served, plain,
                                                       kw):
    """A net of latent layers is not refused the prefix cache or chunked
    prefill: the block answers with its window. Chunks of 8, and a second
    batch that resumes behind a cached prefix, serve the tokens of the
    whole prefill, and the logits behind them are the reference's."""
    w, whole = served
    gen = Generator(whole.net, **GEN, **kw)
    shared = [[7] * 17 + p for p in PROMPTS]
    want = whole.generate(shared, max_new_tokens=4)
    stats = {}
    assert gen.generate(shared, max_new_tokens=4, stats=stats) == want
    if "prefix_cache" in kw:
        again = {}
        assert gen.generate(shared, max_new_tokens=4, stats=again) == want
        assert again["prefix_hit_rate"] > 0.3
        assert min(again["resumed_positions"]) >= 16
    else:
        assert stats["prefill_chunks"] == 5
    lg = ref.logits_at(w, *_sequences(shared, want, 64), n_heads=2)
    assert float(jnp.max(jnp.max(lg, -1) - jnp.take_along_axis(
        lg, jnp.asarray(want)[..., None], -1)[..., 0])) == 0.0


def test_a_resumed_window_writes_the_whole_prefills_rows():
    """One block: 20 positions as one whole prefill, and as 13 cached + a
    resumed window of 7, leave the same latent rows and give the same
    outputs."""
    blk = _block(rope=True, q_lora_rank=24)
    p, _ = blk.initialize(jax.random.PRNGKey(0), None)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 64))
    bs = 4
    tables = jnp.asarray(np.arange(1, 13).reshape(2, 6), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(20), (2, 20))
    slots = attn_ops.paged_slots(tables, pos, bs)
    ones = jnp.ones((2, 20))
    y_whole, whole = blk.prefill_paged(p, x, blk.init_pool(13 * bs), slots,
                                       mask=ones)
    _, part = blk.prefill_paged(p, x[:, :13], blk.init_pool(13 * bs),
                                slots[:, :13], mask=ones[:, :13])
    y_tail, part = blk.prefill_resume_paged(p, x[:, 13:], part, tables,
                                            pos[:, 13:], bs)
    live = np.asarray(slots).ravel()
    np.testing.assert_allclose(np.asarray(part["rows"])[live],
                               np.asarray(whole["rows"])[live], atol=1e-5)
    np.testing.assert_allclose(y_tail, y_whole[:, 13:], atol=1e-4)


def test_written_rows_are_the_latent_rows_and_zero_lanes():
    """One block, rows of 19 and 17 tokens: a prefill of the first 9, a
    resumed window of 4 and six decode steps (the shorter row past its limit
    in the last two) leave, in every slot a live token wrote, lanes 0-31 as
    ``_mla_rows`` computes them at the token's own position and lanes 32-127
    exactly 0; so is every other lane 32-127 of the pool."""
    blk = _block(rope=True, q_lora_rank=24)
    p, _ = blk.initialize(jax.random.PRNGKey(0), None)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 19, 64))
    bs, lens = 4, np.asarray([19, 17])
    tables = jnp.asarray(np.arange(1, 11).reshape(2, 5), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(19), (2, 19))
    slots = np.asarray(attn_ops.paged_slots(tables, pos, bs))
    limits = jnp.asarray(lens - 1, jnp.int32)
    _, pool = blk.prefill_paged(p, x[:, :9], blk.init_pool(11 * bs),
                                jnp.asarray(slots[:, :9]),
                                mask=jnp.ones((2, 9)))
    _, pool = blk.prefill_resume_paged(p, x[:, 9:13], pool, tables,
                                       pos[:, 9:13], bs, limits=limits)
    for t in range(13, 19):
        _, pool = blk.decode_window_paged(p, x[:, t:t + 1], pool, tables,
                                          pos[:, t:t + 1], bs, limits=limits)
    got = np.asarray(pool["rows"])
    assert got.shape == (44, 128)
    assert not got[:, 32:].any()
    want = np.asarray(blk._mla_rows(
        p, rms_norm(x, p["norm1"], blk.eps), pos))
    assert want.shape == (2, 19, 128) and not want[..., 32:].any()
    for i, n in enumerate(lens):
        np.testing.assert_allclose(got[slots[i, :n]], want[i, :n], atol=1e-5)
        assert np.abs(want[i, :n, :32]).sum(-1).min() > 0
    # the row past its limit wrote to the trash block and not to its slots
    assert not got[slots[1, 17:]].any()


# ------------------------------------------------------------ the latent block
def _block(cls=HybridDecoderBlock, **kw):
    """One latent block with weights large enough (N(0, 0.15)) for the
    softmax to see the scores."""
    kw = dict(kv_lora_rank=24, init_range=0.15) | kw
    return cls(hidden_size=64, mixer="mla", ffn="dense", n_heads=2,
               qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, ffn_size=32,
               rope_theta=1e4, **kw)


def _window_against_full(blk, full_blk=None):
    """A window of 3 tokens decoded in absorbed form over paged latent rows
    (rows of 17 and 9 cached tokens) against ``full_blk``'s expanded causal
    attention over the whole sequence -> (got, want)."""
    p, _ = blk.initialize(jax.random.PRNGKey(0), None)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 64))
    full, _ = (full_blk or blk).apply(p, {}, x)
    bs, lens = 4, jnp.asarray([17, 9])
    tables = jnp.asarray(np.arange(1, 13).reshape(2, 6), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(20), (2, 20))
    mask = (pos < lens[:, None]).astype(jnp.float32)
    _, pool = blk.prefill_paged(p, x, blk.init_pool(16 * bs),
                                attn_ops.paged_slots(tables, pos, bs),
                                mask=mask)
    win = lens[:, None] + jnp.arange(3)[None]
    x_w = jnp.take_along_axis(x, win[..., None], axis=1)
    got, _ = blk.decode_window_paged(p, x_w, pool, tables, win, bs)
    return got, jnp.take_along_axis(full, win[..., None], axis=1)


@pytest.mark.parametrize("latent", [24, 120],
                         ids=["padded-32-to-128", "whole-tile-128"])
@pytest.mark.parametrize("rank", [0, 24], ids=["full-rank-q", "low-rank-q"])
@pytest.mark.parametrize("rotate", [False, True], ids=["unrotated", "rope"])
def test_absorbed_window_is_the_expanded_form(rotate, rank, latent):
    """With and without the rotation and the query rank (Kimi's layers are
    the pair without), a row stored wider than the latent width (32 in 128
    lanes, the queries padded to match) and one that is whole tiles as it
    is: the absorbed window over the cached ``[c | RoPE(kr)]`` is the
    expanded attention, rows of unequal length at their own positions."""
    # the wider latent's scores want smaller weights to stay near 0.6
    blk = _block(rope=rotate, q_lora_rank=rank, kv_lora_rank=latent,
                 init_range=0.15 if latent == 24 else 0.1)
    p, _ = blk.initialize(jax.random.PRNGKey(0), None)
    assert blk.init_pool(8)["rows"].shape == (8, 128)
    assert p["Wdkv"].shape == (64, latent + 8)
    assert ("Wdq" in p, "Wq" in p) == (bool(rank), not rank)
    got, want = _window_against_full(blk)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


class _LateRope(HybridDecoderBlock):
    """The fault: the key row is cached as it came and never rotated (the
    rotation left for after the cache write, where the absorbed pass has no
    place for it)."""

    def _mla_rows(self, params, h, positions):
        return HybridDecoderBlock._mla_rows(
            dataclasses.replace(self, rope=False), params, h, positions)


@pytest.mark.parametrize("fault", ["dropped", "after-the-cache-write"])
def test_a_rotation_dropped_or_late_fails(fault):
    right = _block(rope=True)
    wrong = _block(rope=False) if fault == "dropped" \
        else _block(_LateRope, rope=True)
    got, want = _window_against_full(wrong, full_blk=right)
    assert float(jnp.abs(got - want).max()) > 1e-2 * float(
        jnp.abs(want).max())


def test_rope_turns_pairs_of_halves_by_the_position():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 3, 8))
    pos = jnp.asarray([[0, 1, 2, 3, 4], [7, 7, 0, 90, 91]])
    y = np.asarray(rope(x, pos, 100.0))
    np.testing.assert_allclose(y[0, 0], x[0, 0], atol=1e-6)   # position 0
    # each row's own positions: (row 1, column 0) is turned by 7, not by 0
    np.testing.assert_allclose(
        y[1, 0], np.asarray(rope(x[1:, :1], jnp.asarray([[7]]), 100.0))[0, 0],
        atol=1e-6)
    # a pair (i, i + 4) keeps its length; scores depend on distance only
    np.testing.assert_allclose(y[..., 0] ** 2 + y[..., 4] ** 2,
                               x[..., 0] ** 2 + x[..., 4] ** 2, rtol=1e-4)
    q, k = x[0, 0, 0], x[0, 1, 0]
    at = lambda v, t: rope(v[None, None, None], jnp.asarray([[t]]), 100.0)
    dot = lambda a, b: float(jnp.sum(at(q, a) * at(k, b)))
    assert abs(dot(3, 1) - dot(52, 50)) < 1e-4
    assert abs(dot(3, 1) - dot(3, 2)) > 1e-3


# ------------------------------------------------------------------- experts
def test_four_shares_of_sixteen_sum_to_the_uncut_layer_of_64(ref):
    """The published router (top-4 of 64, scaling 1.8) at a small width:
    four chips of 16 experts each, the shared expert counted once, add up to
    the reference's uncut layer (all 64 held, as the cell holds them), and
    each share is the reference's own share."""
    from deeplearning4j_tpu.nn import moe

    cfg = dict(CFG, num_hidden_layers=2, n_routed_experts=64, num_experts=64,
               num_experts_per_tok=4)
    w = ref.make_weights(7, cfg)
    p, d = w["layers"][1], ref._dims(cfg)
    h = jax.random.normal(jax.random.PRNGKey(2), (40, 64))
    mm = lambda a, b: jnp.matmul(a, b, precision="highest")
    uncut = ref._ffn(p, d, h, mm)
    # one expert that no pick names: what is left is the shared expert
    shared = ref._ffn({k: (v[:1] if k[0] == "E" else v)
                       for k, v in p.items()}, dict(d, offset=64), h, mm)
    idx, wts = moe.route_sigmoid_topk(h, p["router"], p["router_bias"], 4,
                                      1.8)
    total, touched = shared, 0
    for off in range(0, 64, 16):
        part, stats = moe.grouped_experts(
            h, idx, wts, p["Egate"][off:off + 16], p["Eup"][off:off + 16],
            p["Edown"][off:off + 16], e_offset=off, n_experts=64)
        share = {k: (v[off:off + 16] if k[0] == "E" else v)
                 for k, v in p.items()}
        want = ref._ffn(share, dict(d, offset=off), h, mm) - shared
        np.testing.assert_allclose(part, want, atol=1e-5)
        assert int(stats[0]) == 160
        touched += int(stats[2])
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=1e-5)
    whole, stats = moe.grouped_experts(h, idx, wts, p["Egate"], p["Eup"],
                                       p["Edown"], e_offset=0, n_experts=64)
    np.testing.assert_allclose(whole + shared, uncut, atol=1e-5)
    assert stats.tolist()[:3] == [160, 160, touched]
    # every pick in one pass when all the experts are held
    assert moe._pass_rows(160, 64, 64) == 160
    assert moe._pass_rows(32 * 1024 * 4, 64, 64) == 131072


# ----------------------------------------------------------- the MTP module
def test_the_mtp_modules_logits_are_the_references(ref, drafted, plain):
    """Behind the prefill the module proposes from the prompt's hidden
    states and the target's pick; behind a verify window from the window's.
    Both are the reference's ``mtp_logits_at`` over the same tokens."""
    w, gen = drafted
    raw, mtp = gen._raw_params(), gen.mtp.params
    tokens, lengths, b_real, lens = gen._prep(PROMPTS, NEW)
    tables_list, addr, *_, held = gen._admit(lens, NEW, 4)
    limits = jnp.asarray([l + NEW - 1 for l in lens] + [0], jnp.int32)
    logits, hidden, gen.pool.pools = gen._prefill_paged_jit(
        raw, gen.pool.pools, tokens, lengths, addr)
    cur = jnp.argmax(logits, -1).astype(jnp.int32)
    first, gen.pool.pools = gen._mtp_prefill_jit(
        raw, mtp, gen.pool.pools, tokens, lengths, addr, hidden, cur)
    # the true continuation as the window: both positions are committed
    window = jnp.asarray([r[:2] for r in plain] + [[0, 0]], jnp.int32)
    assert np.asarray(cur)[:3].tolist() == [r[0] for r in plain]
    vlogits, vhidden, gen.pool.pools = gen._verify_paged_jit(
        raw, gen.pool.pools, addr, window, lengths, limits)
    after = jnp.argmax(vlogits, -1).astype(jnp.int32)
    assert np.asarray(after)[:3].tolist() == [r[1:3] for r in plain]
    second, gen.pool.pools = gen._mtp_draft_jit(
        raw, mtp, gen.pool.pools, addr, after, vhidden, lengths, limits,
        jnp.ones((4,), jnp.int32))
    gen.pool.release(tables_list, held)
    toks, pos = _sequences(PROMPTS, [r[:4] for r in plain])
    want = np.asarray(ref.mtp_logits_at(w, toks, pos))      # (3, 4, V)
    np.testing.assert_allclose(np.asarray(first)[:3], want[:, 0], atol=2e-4)
    np.testing.assert_allclose(np.asarray(second)[:3], want[:, 2], atol=2e-4)


def _oracle(gen, plain, how):
    """The self-draft's two programs with their logits replaced: ``right``
    proposes the token the target will pick, ``wrong`` never does. The
    programs still run (the pools are theirs)."""
    vocab = CFG["vocab_size"]
    real_prefill, real_draft = gen._mtp_prefill_jit, gen._mtp_draft_jit
    lens = [len(p) for p in PROMPTS]

    def propose(at):
        """One-hot logits for output index ``at[i]`` of row i."""
        ids = np.zeros((4,), np.int64)
        for i, row in enumerate(plain):
            true = row[min(int(at[i]), len(row) - 1)]
            ids[i] = true if how == "right" else (true + 1) % vocab
        return jax.nn.one_hot(jnp.asarray(ids), vocab)

    def prefill(*a):
        _, pools = real_prefill(*a)
        return propose([1] * 4), pools

    def draft(raw, mtp, pools, tables, after, hidden, pos0, limits, take):
        _, pools = real_draft(raw, mtp, pools, tables, after, hidden, pos0,
                              limits, take)
        at = np.asarray(pos0)[:3] + np.asarray(take)[:3] + 2 - np.asarray(lens)
        return propose(list(at) + [0]), pools

    gen._mtp_prefill_jit, gen._mtp_draft_jit = prefill, draft
    return real_prefill, real_draft


@pytest.mark.parametrize("how,rate,rounds", [("right", 1.0, 6),
                                             ("wrong", 0.0, NEW - 1),
                                             ("random", None, None)])
def test_drafted_output_is_the_undrafted_token_for_token(drafted, plain, how,
                                                         rate, rounds):
    """Every emitted token is the target's own argmax, whatever the module
    proposes: always right (two tokens a step), always wrong (one), or its
    own proposals under random weights. The counters follow."""
    _, gen = drafted
    tele = tm.get_telemetry()
    names = ("serving.mtp_draft_proposed_total",
             "serving.mtp_draft_accepted_total",
             "serving.prefill_positions_total",
             "serving.prefill_launches_total")
    before = [tele.counter_total(n) for n in names]
    real = _oracle(gen, plain, how) if how != "random" else None
    stats = {}
    try:
        out = gen.generate(PROMPTS, max_new_tokens=NEW, stats=stats)
    finally:
        if real:
            gen._mtp_prefill_jit, gen._mtp_draft_jit = real
    assert out == plain
    moved = [tele.counter_total(n) - b for n, b in zip(names, before)]
    assert moved[2:] == [4 * 32, 1]
    assert 0 <= moved[1] <= moved[0] <= 3 * stats["spec_rounds"]
    if rate is not None:
        # every row runs every round: one proposal a live row a round
        assert moved[:2] == [3 * rounds, 3 * rounds * rate]
        assert stats["spec_accept_rate"] == rate
        assert stats["spec_rounds"] == rounds
    assert "dl4j_serving_mtp_draft_accepted_total" in tele.prometheus_text()


def test_eos_and_sampling_with_a_self_draft(served, drafted, plain):
    """An eos inside an accepted pair ends the row there; a temperature
    falls back to the plain loop (the module's layer rides along
    untouched)."""
    _, gen = drafted
    eos = plain[1][3]
    want = served[1].generate(PROMPTS, max_new_tokens=NEW, eos_id=eos)
    assert gen.generate(PROMPTS, max_new_tokens=NEW, eos_id=eos) == want
    key = jax.random.PRNGKey(5)
    assert gen.generate(PROMPTS, max_new_tokens=5, temperature=0.8,
                        key=key) == \
        served[1].generate(PROMPTS, max_new_tokens=5, temperature=0.8,
                           key=key)


def test_the_modules_rows_are_one_more_layer_of_the_pool(served, drafted):
    """Admitted and freed with the stream, counted by ``bytes_by_kind``;
    warm-up primes the module's two programs; blocks are conserved."""
    _, gen = drafted
    base = served[1].pool_stats()
    s = gen.pool_stats()
    assert s["self_draft"] is True and base["self_draft"] is False
    assert len(gen.pool.pools) == len(served[1].pool.pools) + 1
    assert s["bytes_by_kind"]["tokens"] * 3 == \
        base["bytes_by_kind"]["tokens"] * 4
    free = gen.pool.free_blocks()
    gen.generate(PROMPTS, max_new_tokens=3)
    assert gen.pool.free_blocks() == free
    assert gen.pool.conservation()[0]
    from deeplearning4j_tpu.util import get_watcher
    # two prefill widths (32, 96), each with the module's; verify; draft
    assert gen.warmup() == served[1].warmup() + 2 + 2
    traces = get_watcher().counts()["total_traces"]
    gen.generate(PROMPTS, max_new_tokens=3)
    assert get_watcher().counts()["total_traces"] == traces
    assert gen.health_probe()


@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefill_chunk=8), "prefill_chunk"),
    (dict(draft_net="a net"), "draft_net"),
])
def test_a_self_draft_refuses_what_its_rows_cannot_follow(built, kw, what):
    w, net, builder = built
    with pytest.raises(ValueError, match=what):
        Generator(net, self_draft=builder.self_draft(CFG, w),
                  **{**GEN, **kw})


def test_a_recurrent_net_refuses_a_self_draft():
    from deeplearning4j_tpu.zoo import KimiLinear

    with pytest.raises(ValueError, match="self_draft"):
        Generator(KimiLinear.tiny().network(), max_length=96,
                  batch_buckets=(4,), self_draft=SelfDraft(None))
