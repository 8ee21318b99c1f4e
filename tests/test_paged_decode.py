"""Paged KV cache + speculative decoding + int8 serving (ISSUE 15).

The acceptance contracts: paged-cache greedy decode TOKEN-IDENTICAL to the
contiguous r13 cache (and the O(T²) recompute oracle) for ragged prompts
crossing page boundaries; speculative greedy TOKEN-IDENTICAL to
non-speculative greedy — including a draft that is always wrong (k
rejections per round); eos mid-speculation-window; temperature>0 falling
back to verify-consistent sampling; pool exhaustion as a first-class 429
shed with blocks freed and reused; int8 round-trip through ModelSerializer
archives within the pinned tolerance with the fp32 path bit-unchanged;
ONE decode executable serving mixed context lengths with 0 steady-state
recompiles."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.serving import (BatchScheduler, Generator,
                                        INT8_LOGIT_TOL, ModelRouter,
                                        PoolExhaustedError, ServingModel)
from deeplearning4j_tpu.ops import attention as attn_ops
from deeplearning4j_tpu.util import telemetry as tm
from deeplearning4j_tpu.util.compile_watcher import get_watcher
from deeplearning4j_tpu.util.model_serializer import ModelSerializer
from deeplearning4j_tpu.zoo.bert import Bert

VOCAB = 43
MAXLEN = 32
BUCKETS = dict(batch_buckets=(1, 2, 4), prefill_buckets=(8, 16))

#: ragged prompts whose contexts CROSS page boundaries at block_size=4
#: (lengths 3/5/9 → 1/2/3 blocks before decoding even starts)
RAGGED = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10, 11, 12, 13, 14, 15, 16, 17]]


@pytest.fixture(scope="module")
def target_net():
    return Bert.tiny(causal=True, task="mlm", vocab_size=VOCAB,
                     max_length=MAXLEN, hidden_dropout=0.0).init()


@pytest.fixture(scope="module")
def draft_net():
    return Bert.draft(vocab_size=VOCAB, max_length=MAXLEN, seed=7).init()


@pytest.fixture(scope="module")
def gen_contiguous(target_net):
    return Generator(target_net, paged=False, **BUCKETS)


@pytest.fixture(scope="module")
def gen_paged(target_net):
    return Generator(target_net, paged=True, block_size=4, **BUCKETS)


@pytest.fixture(scope="module")
def gen_spec(target_net, draft_net):
    return Generator(target_net, paged=True, block_size=4,
                     draft_net=draft_net, spec_tokens=3, **BUCKETS)


@pytest.fixture(scope="module")
def ref_tokens(gen_contiguous):
    return gen_contiguous.generate(RAGGED, max_new_tokens=8)


class TestPagedIdentity:
    def test_paged_equals_contiguous_and_recompute(self, gen_paged,
                                                   gen_contiguous,
                                                   ref_tokens):
        """The acceptance bit: paged greedy == contiguous greedy == O(T²)
        recompute, token-for-token, on ragged page-boundary-crossing
        prompts."""
        paged = gen_paged.generate(RAGGED, max_new_tokens=8)
        assert paged == ref_tokens
        assert paged == gen_contiguous.generate_full_recompute(
            RAGGED, max_new_tokens=8)
        assert all(len(r) == 8 for r in paged)

    def test_blocks_freed_after_batch(self, gen_paged):
        pool = gen_paged.pool
        assert pool.free_blocks() == pool.num_blocks
        gen_paged.generate([[1, 2, 3, 4, 5]], max_new_tokens=4)
        assert pool.free_blocks() == pool.num_blocks

    def test_sampled_paged_equals_contiguous(self, gen_paged,
                                             gen_contiguous):
        """temperature>0: the paged loop consumes the same key stream, so
        sampled output is identical too (stream-exact)."""
        key = jax.random.PRNGKey(11)
        a = gen_paged.generate(RAGGED, max_new_tokens=6, temperature=0.7,
                               key=key)
        b = gen_contiguous.generate(RAGGED, max_new_tokens=6,
                                    temperature=0.7, key=key)
        assert a == b

    def test_one_executable_mixed_context_lengths(self, gen_paged):
        """ONE decode executable serves mixed context lengths: after
        warmup, batches at wildly different context lengths trace
        NOTHING."""
        gen_paged.warmup()
        w = get_watcher()
        with w.scope() as s:
            gen_paged.generate([[1, 2]], max_new_tokens=4)
            gen_paged.generate([[i % VOCAB for i in range(20)]],
                               max_new_tokens=4)
            gen_paged.generate(RAGGED, max_new_tokens=4)
        assert s.traces == 0, f"steady-state decode traced {s.traces}x"

    def test_eos_early_exit_frees_blocks_and_trims(self, gen_paged,
                                                   gen_contiguous):
        ref = gen_contiguous.generate([[1, 2, 3]], max_new_tokens=8)
        eos = ref[0][2]  # third generated token
        out = gen_paged.generate([[1, 2, 3]], max_new_tokens=8, eos_id=eos)
        want = ref[0][:ref[0].index(eos) + 1]
        assert out[0] == want
        assert gen_paged.pool.free_blocks() == gen_paged.pool.num_blocks


# --------------------------------------------------------------------------
# The block-chunked pass itself (ISSUE 28): ops/attention.paged_attention
# against the dense masked softmax over every declared position.

A_B, A_H, A_DH, A_BS = 32, 2, 8, 16
A_WIDTH = 18                      # table blocks: 288 positions
A_CHUNK = A_BS * attn_ops.paged_chunk_blocks(A_B, A_WIDTH, A_BS)


def _paged_case(w, max_pos, seed=0):
    """Random logical K/V (B, L, H*Dh) scattered into a slot-flat pool
    through page tables in shuffled physical order; rows 0 and 1 SHARE
    their first three blocks (a prefix-cache hit). Query positions are
    ragged below ``max_pos``, row 2 holds ``max_pos`` itself."""
    rng = np.random.default_rng(seed)
    length = A_WIDTH * A_BS
    hd = A_H * A_DH
    k = rng.normal(size=(A_B, length, hd)).astype(np.float32)
    v = rng.normal(size=(A_B, length, hd)).astype(np.float32)
    k[1, :3 * A_BS], v[1, :3 * A_BS] = k[0, :3 * A_BS], v[0, :3 * A_BS]
    ids = rng.permutation(np.arange(1, A_B * A_WIDTH + 1))
    tables = ids.reshape(A_B, A_WIDTH).astype(np.int32)
    tables[1, :3] = tables[0, :3]
    pool_k = np.zeros(((A_B * A_WIDTH + 1) * A_BS, hd), np.float32)
    pool_v = np.zeros_like(pool_k)
    for b in range(A_B):
        for j in range(A_WIDTH):
            rows = slice(tables[b, j] * A_BS, (tables[b, j] + 1) * A_BS)
            pool_k[rows] = k[b, j * A_BS:(j + 1) * A_BS]
            pool_v[rows] = v[b, j * A_BS:(j + 1) * A_BS]
    last = rng.integers(w - 1, max_pos, size=A_B)
    last[2] = max_pos
    positions = (last[:, None] - (w - 1) + np.arange(w)[None, :]).astype(
        np.int32)
    q = rng.normal(size=(A_B, A_H, w, A_DH)).astype(np.float32)
    return q, k, v, pool_k, pool_v, tables, positions


def _dense_reference(q, k, v, positions):
    """The parent's attention: every declared position gathered into
    (B, H, L, Dh), one masked softmax over all of them."""
    split = lambda y: jnp.transpose(
        jnp.asarray(y).reshape(A_B, -1, A_H, A_DH), (0, 2, 1, 3))
    amask = (jnp.arange(k.shape[1])[None, None, :]
             <= positions[:, :, None])[:, None]
    return attn_ops.dot_product_attention(jnp.asarray(q), split(k),
                                          split(v), mask=amask)


class TestChunkedPagedAttention:
    def test_case_shape(self):
        """What the cases below rely on: several turns, and a table that
        is no multiple of the chunk (its padding is the trash block)."""
        assert A_CHUNK == 64
        assert (A_WIDTH * A_BS) % A_CHUNK != 0

    @pytest.mark.parametrize("w", [1, 4])
    @pytest.mark.parametrize("max_pos", [40, 150, 287])
    def test_matches_dense_masked_reference(self, w, max_pos):
        """Ragged positions, W = 1 (decode) and 4 (verify window), one to
        five turns, two rows sharing blocks: within 1e-5 of the dense
        masked softmax over every declared position."""
        q, k, v, pk, pv, tables, pos = _paged_case(w, max_pos)
        got = attn_ops.paged_attention(q, pk, pv, tables, pos, A_BS)
        want = _dense_reference(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=0)

    @pytest.mark.parametrize("w", [1, 4])
    def test_skipping_is_real(self, w):
        """K/V past the last live chunk are never read: NaN there leaves
        the output finite and EQUAL, where the dense pass turns NaN x 0
        into NaN. A NaN inside a live position still shows."""
        max_pos = 100                                   # two turns of 64
        q, k, v, pk, pv, tables, pos = _paged_case(w, max_pos, seed=1)
        clean = np.asarray(attn_ops.paged_attention(q, pk, pv, tables, pos,
                                                    A_BS))
        first_dead = 2 * A_CHUNK // A_BS                # block column 8
        dead = tables[:, first_dead:].reshape(-1)
        pk2, pv2 = pk.copy(), pv.copy()
        for blk in dead:
            pk2[blk * A_BS:(blk + 1) * A_BS] = np.nan
            pv2[blk * A_BS:(blk + 1) * A_BS] = np.nan
        got = np.asarray(attn_ops.paged_attention(q, pk2, pv2, tables, pos,
                                                  A_BS))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, clean)
        k2, v2 = k.copy(), v.copy()
        k2[:, 2 * A_CHUNK:], v2[:, 2 * A_CHUNK:] = np.nan, np.nan
        assert np.isnan(np.asarray(_dense_reference(q, k2, v2, pos))).any()
        # a live position: row 2's key at position 5
        slot = tables[2, 0] * A_BS + 5
        pk3 = pk.copy()
        pk3[slot] = np.nan
        hit = np.asarray(attn_ops.paged_attention(q, pk3, pv, tables, pos,
                                                  A_BS))
        assert np.isnan(hit[2]).all()
        assert np.isfinite(hit[3:]).all()

    def test_slots_past_the_table_land_in_trash(self):
        tables = jnp.asarray([[3, 7]], jnp.int32)
        pos = jnp.asarray([[0, 5, 9, 40]], jnp.int32)
        got = attn_ops.paged_slots(tables, pos, 4)
        assert got.tolist() == [[12, 29, 1, 0]]         # 9 -> blk 2: trash


# --------------------------------------------------------------------------
# The same through the compiled decode step, at a size where the table is
# four chunks wide.

L_MAXLEN, L_BS, L_BATCH = 256, 16, 32


@pytest.fixture(scope="module")
def gen_long():
    net = Bert.tiny(causal=True, task="mlm", vocab_size=VOCAB,
                    max_length=L_MAXLEN, hidden_dropout=0.0).init()
    return Generator(net, paged=True, block_size=L_BS,
                     batch_buckets=(L_BATCH,), prefill_buckets=(8, 256),
                     model_id="kv-read")


def _kv_counters():
    tele = tm.get_telemetry()
    return [tele.counter_total(f"serving.decode_kv_positions_{n}_total",
                               model="kv-read")
            for n in ("read", "declared")]


class TestDecodeReadsWhatStreamsHold:
    def test_compiled_step_gathers_no_declared_length(self, gen_long):
        """The compiled ``_decode_paged`` holds a loop and no array of
        batch x max_length x H x Dh elements beside the pools it was
        given: what a turn gathers is batch x chunk rows."""
        b = L_BATCH
        i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
        tables = i32(b, gen_long.pool.max_blocks_per_stream)
        text = gen_long._decode_paged_jit.lower(
            gen_long._raw_params(), gen_long.pool.pools, tables, i32(b),
            i32(b), i32(b)).compile().as_text()
        assert re.search(r"\bwhile\(", text)
        hidden = gen_long.blocks[0].hidden_size
        pool_size = gen_long.pool.pools[0]["k"].size    # in any view
        declared = b * L_MAXLEN * hidden
        chunk = L_BS * attn_ops.paged_chunk_blocks(b, L_MAXLEN // L_BS, L_BS)
        assert chunk == 64 < L_MAXLEN
        sizes = []
        for dims in re.findall(r"f32\[([0-9,]+)\]", text):
            size = int(np.prod([int(d) for d in dims.split(",")]))
            if size != pool_size:
                sizes.append(size)
        assert max(sizes) < declared
        assert b * chunk * hidden in sizes          # one turn's gather

    def test_counter_two_token_prompt_and_full_stream(self, gen_long):
        """``decode_kv_read_share``: chunk / max_length for a two-token
        prompt, 1.0 for a stream that ends at max_length."""
        assert gen_long.pool_stats()["decode_kv_read_share"] is None
        r0, d0 = _kv_counters()
        gen_long.generate([[1, 2]], max_new_tokens=4)
        r1, d1 = _kv_counters()
        assert d1 - d0 == 3 * L_BATCH * L_MAXLEN         # 3 decode steps
        assert (r1 - r0) / (d1 - d0) == 64 / L_MAXLEN
        assert gen_long.pool_stats()["decode_kv_read_share"] == 0.25
        long_prompt = [(i % (VOCAB - 1)) + 1 for i in range(L_MAXLEN - 6)]
        out = gen_long.generate([long_prompt], max_new_tokens=6)
        assert len(out[0]) == 6
        r2, d2 = _kv_counters()
        assert d2 - d1 == 5 * L_BATCH * L_MAXLEN
        assert (r2 - r1) / (d2 - d1) == 1.0
        share = gen_long.pool_stats()["decode_kv_read_share"]
        assert share == round((3 * 64 + 5 * 256) / (8 * 256), 4)

    def test_long_context_tokens_match_contiguous(self, gen_long):
        """Four turns deep: the chunked pass still gives the contiguous
        cache's tokens, ragged rows beside a long one."""
        contiguous = Generator(gen_long.net, paged=False,
                               batch_buckets=(4,),
                               prefill_buckets=(8, 256))
        prompts = [[(7 * i) % (VOCAB - 1) + 1 for i in range(200)],
                   [3, 1, 4, 1, 5], [9] * 70]
        ref = contiguous.generate(prompts, max_new_tokens=8)
        assert gen_long.generate(prompts, max_new_tokens=8) == ref


class TestSpeculative:
    def test_spec_greedy_token_identical(self, gen_spec, ref_tokens):
        stats = {}
        out = gen_spec.generate(RAGGED, max_new_tokens=8, stats=stats)
        assert out == ref_tokens
        rates = stats["draft_accept_rate"]
        assert len(rates) == len(RAGGED)
        assert all(r is not None and 0.0 <= r <= 1.0 for r in rates)
        assert stats["spec_rounds"] >= 1

    def test_self_draft_accepts_everything(self, target_net, ref_tokens):
        """draft == target: every proposal verifies, accept rate 1.0 and
        far fewer rounds than tokens."""
        gen = Generator(target_net, paged=True, block_size=4,
                        draft_net=target_net, spec_tokens=3, **BUCKETS)
        stats = {}
        out = gen.generate(RAGGED, max_new_tokens=8, stats=stats)
        assert out == ref_tokens
        assert stats["spec_accept_rate"] == 1.0
        # 1 prefill token + ceil(7 / 4) fully-accepted windows
        assert stats["spec_rounds"] <= 3

    def test_draft_always_wrong_still_identical(self, gen_spec,
                                                ref_tokens):
        """k rejections per round: a draft proposing (token+1) mod V —
        essentially never the target's argmax — still yields the exact
        greedy sequence, one token per round (the correction token is the
        target's own logits)."""
        draft = gen_spec.draft
        orig = draft._decode_jit
        try:
            def wrong(raw, caches, tokens, positions):
                return (jax.nn.one_hot((tokens + 1) % VOCAB, VOCAB),
                        caches)

            draft._decode_jit = wrong
            stats = {}
            out = gen_spec.generate(RAGGED, max_new_tokens=8, stats=stats)
        finally:
            draft._decode_jit = orig
        assert out == ref_tokens
        assert stats["spec_accept_rate"] <= 0.25  # wrong ~always

    def test_eos_mid_speculation_window(self, gen_spec, gen_contiguous):
        """eos landing INSIDE an accepted window trims exactly like the
        non-speculative path."""
        prompts = [RAGGED[0], RAGGED[1]]
        ref = gen_contiguous.generate(prompts, max_new_tokens=8)
        eos = ref[0][3]  # 4th token: mid-window at spec_tokens=3
        out = gen_spec.generate(prompts, max_new_tokens=8, eos_id=eos)
        want = [r[:r.index(eos) + 1] if eos in r else r for r in ref]
        assert out == want
        assert gen_spec.pool.free_blocks() == gen_spec.pool.num_blocks

    def test_temperature_falls_back_to_plain_sampling(self, gen_spec,
                                                      gen_contiguous):
        """The verify-consistent sampling satellite: temperature>0 on a
        speculating generator routes through the plain per-token loop —
        identical streams to the non-speculative path."""
        key = jax.random.PRNGKey(3)
        a = gen_spec.generate(RAGGED, max_new_tokens=6, temperature=0.9,
                              key=key)
        b = gen_contiguous.generate(RAGGED, max_new_tokens=6,
                                    temperature=0.9, key=key)
        assert a == b

    @pytest.mark.parametrize("rows", [1, 3])
    def test_a_models_own_mtp_head_drafts_through_the_serving_model(
            self, rows):
        """The self-draft (a latent-attention expert model's MTP module in
        place of a draft net) through ``ServingModel``: ragged rows crossing
        page boundaries come out as undrafted greedy decoding serves them,
        the module's rows ride the same pool, and its blocks come back."""
        from deeplearning4j_tpu.zoo import Glm4MoeLite

        zoo = Glm4MoeLite.tiny(vocab_size=VOCAB, max_length=MAXLEN)
        net = zoo.init()
        draft = zoo.mtp()
        draft.params = draft.module.initialize(jax.random.PRNGKey(9))
        kw = dict(kind="generate", paged=True, block_size=4,
                  max_length=MAXLEN, bucketing="batch=1,2,4;seq=8,16")
        plain = ServingModel(net, "plain", **kw)
        model = ServingModel(net, "drafted", self_draft=draft, **kw)
        prompts = RAGGED[:rows]
        want = plain.generator.generate(prompts, max_new_tokens=8)
        stats = {}
        assert model.generator.generate(prompts, max_new_tokens=8,
                                        stats=stats) == want
        assert len(stats["draft_accept_rate"]) == rows
        assert model.describe()["speculative"] == {"spec_tokens": 1,
                                                   "self_draft": True}
        pool = model.generator.pool
        assert len(pool.pools) == len(plain.generator.pool.pools) + 1
        assert pool.free_blocks() == pool.num_blocks
        assert pool.conservation()[0]


class TestPoolExhaustion:
    def test_exhaustion_sheds_and_blocks_reused(self, target_net):
        """All-or-nothing admission: an over-pool batch sheds with nothing
        allocated, and the freed pool serves the next batch (block
        free/reuse after shed)."""
        gen = Generator(target_net, paged=True, block_size=4,
                        pool_blocks=4, batch_buckets=(1, 2, 4),
                        prefill_buckets=(8,))
        with pytest.raises(PoolExhaustedError):
            gen.generate([[1] * 8, [2] * 8, [3] * 8], max_new_tokens=8)
        assert gen.pool.free_blocks() == gen.pool.num_blocks
        out = gen.generate([[1, 2, 3]], max_new_tokens=8)  # 3 blocks
        assert len(out[0]) == 8
        assert gen.pool.free_blocks() == gen.pool.num_blocks

    def test_scheduler_first_class_shed(self, target_net):
        """The r13 shed contract, new cause: PoolExhaustedError through
        the scheduler is a shed (429 + Retry-After via ShedError), with
        its own flight-recorder cause and per-lane counter — never an
        error, never a breaker outcome."""
        model = ServingModel(target_net, "small-pool", kind="generate",
                             bucketing="batch=1,2;seq=8", block_size=4,
                             pool_blocks=2)
        model.warmup()
        sched = BatchScheduler(model, max_wait_ms=1.0)
        sched.start()
        try:
            fut = sched.submit(np.asarray([1] * 8, np.int32),
                               max_new_tokens=20)  # needs 7 blocks > 2
            with pytest.raises(PoolExhaustedError):
                fut.result(timeout=30)
            assert sched.counts["shed_pool_exhausted"] == 1
            assert sched.counts["errors"] == 0
            assert sched.lane_counts["interactive"][
                "shed_pool_exhausted"] == 1
            rec = sched.flight.dump(last=1)[0]
            assert rec["status"] == "shed"
            assert rec["cause"] == "pool_exhausted"
            assert sched.breaker.state == "closed"
            # pool freed: a fitting request decodes fine afterwards
            fut2 = sched.submit(np.asarray([1, 2], np.int32),
                                max_new_tokens=4)
            assert len(fut2.result(timeout=30)) == 4
        finally:
            sched.shutdown()

    def test_auto_pool_grows_instead_of_shedding(self, target_net):
        """An AUTO-sized pool (no operator budget) must never refuse a
        batch the contiguous engine would have served: exhaustion grows
        the pool (review finding r20). A PINNED pool keeps the shed."""
        gen = Generator(target_net, paged=True, block_size=4,
                        batch_buckets=(1, 2, 4), prefill_buckets=(8,))
        # shrink the auto pool under the batch's need, keeping auto mode
        gen.pool = type(gen.pool)(gen.blocks, block_size=4, num_blocks=4,
                                  max_length=gen.max_length)
        assert gen._pool_auto
        out = gen.generate([[1] * 8, [2] * 8, [3] * 8],
                           max_new_tokens=8)  # needs 12 > 4 blocks
        assert all(len(r) == 8 for r in out)
        assert gen.pool.num_blocks >= 12
        assert gen.pool.free_blocks() == gen.pool.num_blocks

    def test_stream_accounting(self, target_net):
        gen = Generator(target_net, paged=True, block_size=4,
                        pool_blocks=24, batch_buckets=(1, 2, 4),
                        prefill_buckets=(8,))
        gen.generate(RAGGED, max_new_tokens=4)
        st = gen.pool.stats()
        assert st["peak_streams"] == 3
        assert st["streams"] == 0
        assert st["contiguous_stream_ceiling"] == (24 * 4) // MAXLEN


class TestInt8Serving:
    def test_resident_bytes_and_tolerance(self, target_net, gen_paged):
        """Acceptance: resident int8 bytes ≥3.5× below fp32, prefill
        logits within the pinned tolerance, decode runs end to end."""
        gen = Generator(target_net, paged=True, block_size=4,
                        quantize="int8", **BUCKETS)
        qp = gen._qp
        assert qp.fp32_bytes() / qp.resident_bytes() >= 3.5
        tokens = jnp.asarray(np.asarray([RAGGED[1] + [0] * 3], np.int32))
        lengths = jnp.asarray([5], jnp.int32)
        tables = jnp.zeros((1, gen.pool.max_blocks_per_stream), jnp.int32)
        ql, pools = gen._prefill_paged_jit(gen._raw_params(),
                                           gen.pool.pools, tokens,
                                           lengths, tables)
        gen.pool.pools = pools
        t2 = jnp.zeros((1, gen_paged.pool.max_blocks_per_stream),
                       jnp.int32)
        fl, fpools = gen_paged._prefill_paged_jit(
            gen_paged._raw_params(), gen_paged.pool.pools, tokens,
            lengths, t2)
        gen_paged.pool.pools = fpools
        assert float(jnp.max(jnp.abs(ql - fl))) <= INT8_LOGIT_TOL
        out = gen.generate(RAGGED, max_new_tokens=6)
        assert all(len(r) == 6 for r in out)

    def test_fp32_path_bit_unchanged(self, target_net, gen_paged,
                                     ref_tokens):
        """Quantization is strictly opt-in: building an int8 generator
        mutates nothing, and the fp32 generator's output is bit-unchanged
        next to it."""
        before = [np.asarray(x).copy()
                  for x in jax.tree_util.tree_leaves(target_net.params)]
        Generator(target_net, paged=True, block_size=4, quantize="int8",
                  **BUCKETS)
        after = jax.tree_util.tree_leaves(target_net.params)
        assert all(np.array_equal(b, np.asarray(a))
                   for b, a in zip(before, after))
        assert gen_paged.generate(RAGGED, max_new_tokens=8) == ref_tokens

    def test_archive_roundtrip(self, target_net, tmp_path):
        """int8 round-trip through ModelSerializer: archive ~4× smaller,
        the stored quantization adopted VERBATIM on load (bit-identical
        to the pre-save quantized serving), and plain restore dequantizes
        to a usable fp32 net."""
        fp32 = str(tmp_path / "m.zip")
        int8 = str(tmp_path / "m8.zip")
        ModelSerializer.write_model(target_net, fp32, save_updater=False)
        ModelSerializer.write_model(target_net, int8, quantize="int8")
        assert os.path.getsize(fp32) / os.path.getsize(int8) >= 3.5
        meta = ModelSerializer.peek_meta(int8)
        assert meta["quantize"] == "int8"

        mem = Generator(target_net, paged=True, block_size=4,
                        quantize="int8", batch_buckets=(1, 2),
                        prefill_buckets=(8,))
        want = mem.generate(RAGGED[:2], max_new_tokens=6)

        router = ModelRouter("int8-rt")
        try:
            router.load("q8", int8, kind="generate", quantize="int8",
                        bucketing="batch=1,2;seq=8", block_size=4)
            model, _ = router.get("q8")
            model.warmup()
            got, _ = model.execute(
                [np.asarray(p, np.int32) for p in RAGGED[:2]],
                max_new_tokens=6)
            assert list(got) == want
            # the archive's quantization was adopted, not recomputed
            assert model.generator._qp is not None
        finally:
            router.shutdown()

        # plain restore: a dequantized fp32 net, params within tolerance
        net2 = ModelSerializer.restore_model(int8)
        a = jax.tree_util.tree_leaves(target_net.params)
        b = jax.tree_util.tree_leaves(net2.params)
        for x, y in zip(a, b):
            x, y = np.asarray(x), np.asarray(y)
            if x.ndim >= 2 and x.size >= 256:
                scale = np.abs(x).max() / 127.0
                assert np.max(np.abs(x - y)) <= scale + 1e-6
            else:
                assert np.array_equal(x, y)

    def test_stale_int8_stash_not_served(self, target_net, tmp_path):
        """A net restored from an int8 archive and then MUTATED must not
        serve the stale archived quantization (review finding r20): the
        stash is validated against the live params and falls through to
        fresh quantization."""
        from deeplearning4j_tpu.serving.quantize import maybe_quantize

        path = str(tmp_path / "m8.zip")
        ModelSerializer.write_model(target_net, path, quantize="int8")
        net = ModelSerializer.restore_model(path)
        assert getattr(net, "_int8_archive", None) is not None
        qp0 = maybe_quantize(net, "int8")  # untouched: stash adopted
        assert np.array_equal(np.asarray(qp0.qleaves[0]),
                              np.asarray(net._int8_archive[1][0]))
        # mutate the live params — the stash is now stale
        leaves = jax.tree_util.tree_leaves(net.params)
        big = max(range(len(leaves)), key=lambda i: leaves[i].size)
        mutated = [np.asarray(l).copy() for l in leaves]
        mutated[big] = mutated[big] + 1.0
        net.params = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(net.params), mutated)
        qp1 = maybe_quantize(net, "int8")
        deq = np.asarray(qp1.qleaves[big], np.float32) * qp1.scales[big]
        assert np.max(np.abs(deq - mutated[big])) <= float(
            np.abs(mutated[big]).max() / 127.0) + 1e-6

    def test_resident_bytes_no_host_copy(self, target_net):
        """resident_bytes reads .nbytes without np.asarray — it runs on
        every status poll (review finding r20)."""
        from deeplearning4j_tpu.serving.quantize import QuantizedParams

        qp = QuantizedParams.from_params(target_net.params).device_put()
        assert qp.resident_bytes() > 0
        assert qp.fp32_bytes() / qp.resident_bytes() >= 3.5

    @staticmethod
    def _dense_net(seed=0):
        from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Adam

        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Adam(1e-3))
                .batch_buckets((2, 4)).list()
                .layer(DenseLayer(n_in=12, n_out=48, activation="relu"))
                .layer(OutputLayer(n_in=48, n_out=5, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.feed_forward(12)).build())
        return MultiLayerNetwork(conf).init()

    def test_int8_classify_within_tolerance(self):
        """The classify leg: int8 ServingModel output within tolerance of
        the fp32 forward; the fp32 ServingModel stays bit-exact."""
        net = self._dense_net()
        x = np.random.default_rng(0).normal(size=(3, 12)).astype(np.float32)
        ref = np.asarray(net.output(x))

        q = ServingModel(net, "q-clf", quantize="int8")
        q.warmup()
        got, _ = q.execute([x])
        assert np.max(np.abs(np.asarray(got[0]) - ref)) <= INT8_LOGIT_TOL

        f = ServingModel(net, "f-clf")
        f.warmup()
        got32, _ = f.execute([x])
        assert np.array_equal(np.asarray(got32[0]), ref)

    def test_int8_classify_reload_serves_new_weights(self, tmp_path):
        """Rolling reload of an int8 classify model must swap the
        quantized residents WITH the net (review finding r20): the
        post-reload output tracks the NEW weights, not the old int8
        closure."""
        net_a = self._dense_net(seed=0)
        net_b = self._dense_net(seed=9)  # same topology, new weights
        path = str(tmp_path / "b.zip")
        ModelSerializer.write_model(net_b, path, save_updater=False)
        x = np.random.default_rng(1).normal(size=(3, 12)).astype(np.float32)

        router = ModelRouter("int8-reload")
        try:
            router.register(ServingModel(net_a, "clf", quantize="int8"),
                            start=False)
            model, _sched = router.get("clf")
            model.warmup()
            before, _ = model.execute([x])
            version = router.reload("clf", path)
            assert version == 2
            after, _ = model.execute([x])
            ref_b = np.asarray(net_b.output(x))
            assert np.max(np.abs(np.asarray(after[0]) - ref_b)) \
                <= INT8_LOGIT_TOL
            assert not np.array_equal(np.asarray(before[0]),
                                      np.asarray(after[0]))
        finally:
            router.shutdown()


# ---------------------------------------------------------------------------
# the same contracts as a client of the HTTP server sees them
# ---------------------------------------------------------------------------


class TestDecodeOverHttp:
    """One server for the class, loaded the way an operator loads it: a
    speculative target whose draft rides in from its own archive, an int8
    model from an int8 archive, and a decoder over a deliberately small
    pinned pool."""

    @pytest.fixture(scope="class")
    def served(self, target_net, draft_net, tmp_path_factory):
        from deeplearning4j_tpu.serving import ModelServer

        tmp = tmp_path_factory.mktemp("decode-http")
        fp32, int8, draft = (str(tmp / n) for n in
                             ("bert.zip", "bert-int8.zip", "draft.zip"))
        ModelSerializer.write_model(target_net, fp32, save_updater=False)
        ModelSerializer.write_model(target_net, int8, quantize="int8")
        ModelSerializer.write_model(draft_net, draft, save_updater=False)
        buckets = "batch=1,2,4;seq=8,16"
        router = ModelRouter(name="decode-http")
        router.load("spec", fp32, kind="generate", bucketing=buckets,
                    block_size=4, draft_path=draft, spec_tokens=3)
        router.load("int8", int8, kind="generate", bucketing=buckets,
                    block_size=4, quantize="int8")
        # 24 blocks of 4 = 96 slots: the contiguous ceiling is 96 // 32 = 3
        # streams, four short prompts fit paged, eight long ones do not
        router.register(ServingModel(target_net, "tiny-pool",
                                     kind="generate", bucketing=buckets,
                                     block_size=4, pool_blocks=24),
                        max_wait_ms=1.0, queue_limit=64)
        server = ModelServer(router, port=0).start()  # warms every bucket
        yield server, router
        server.stop()

    def test_speculative_traffic_token_identical_and_compiles_nothing(
            self, served, ref_tokens, http_json, all_at_once):
        server, _router = served
        rec0 = tm.get_telemetry().counter_total("serving.recompiles_total")
        got = all_at_once(
            lambda prompt: http_json(
                f"{server.url}/v1/models/spec/generate",
                {"prompt_tokens": [prompt], "max_new_tokens": 8,
                 "lane": "batch"}), RAGGED)
        assert [g[0] for g in got] == [200] * len(RAGGED)
        assert [g[1]["tokens"][0] for g in got] == ref_tokens
        assert tm.get_telemetry().counter_total(
            "serving.recompiles_total") == rec0
        # what an operator reads of the speculation
        _code, dump, _h = http_json(
            f"{server.url}/v1/models/spec/debug/requests")
        assert any("draft_accept_rate" in r for r in dump["requests"]
                   if r["status"] == "ok")
        _code, status, _h = http_json(f"{server.url}/v1/models")
        assert status["models"]["spec"]["speculative"]["spec_tokens"] == 3
        assert "kv_pool" in status["models"]["spec"]
        _code, text, _h = http_json(f"{server.url}/metrics")
        for series in ("serving_spec_accept_rate",
                       "serving_kv_pool_blocks_free",
                       "serving_concurrent_streams"):
            assert series in text, series

    def test_pool_exhaustion_is_429_with_retry_after_then_blocks_reused(
            self, served, http_json):
        server, router = served
        url = f"{server.url}/v1/models/tiny-pool/generate"
        code, body, hdrs = http_json(url, {"prompt_tokens": [[7] * 20] * 8,
                                           "max_new_tokens": 8})
        assert code == 429 and body["error"] == "PoolExhaustedError"
        assert int(hdrs["Retry-After"]) >= 1
        _code, dump, _h = http_json(
            f"{server.url}/v1/models/tiny-pool/debug/requests")
        assert "pool_exhausted" in [r.get("cause")
                                    for r in dump["requests"]]
        code, body, _h = http_json(
            url, {"prompt_tokens": RAGGED + [RAGGED[0]],
                  "max_new_tokens": 4})
        assert code == 200 and len(body["tokens"]) == 4
        pool = router.get("tiny-pool")[0].generator.pool
        assert pool.peak_streams > pool.contiguous_stream_ceiling()

    def test_int8_archive_serves_beside_fp32_at_a_quarter_of_the_bytes(
            self, served, http_json):
        server, router = served
        code, body, _h = http_json(
            f"{server.url}/v1/models/int8/generate",
            {"prompt_tokens": RAGGED, "max_new_tokens": 6})
        assert code == 200 and [len(r) for r in body["tokens"]] == [6] * 3
        qp = router.get("int8")[0].generator._qp
        assert qp.fp32_bytes() / qp.resident_bytes() >= 3.5
        _code, text, _h = http_json(f"{server.url}/metrics")
        assert "serving_weight_bytes" in text
