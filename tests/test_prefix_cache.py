"""Shared-prefix KV reuse + chunked prefill (ISSUE 16).

The acceptance contracts: prefix-shared decode TOKEN-IDENTICAL to the
unshared paged path and the O(T²) recompute oracle — greedy, sampled
(stream-exact) and speculative — across cold cache, warm cache and the
COW-split case (block-aligned full-prompt hit); eos early-exit and
rollback decrement refcounts instead of freeing shared blocks; block
refcount conservation holds across randomized interleavings of (admit,
share, COW-split, eos, rollback, pool-grow, exception-reset) and is
asserted by the health probe; chunked prefill is window-width-invariant;
mixed hit/miss + chunked traffic traces NOTHING after warmup."""

import random

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.serving import (BatchScheduler, BlockPool,
                                        Generator, PrefixCache,
                                        ServingModel)
from deeplearning4j_tpu.util import telemetry as tm
from deeplearning4j_tpu.util.compile_watcher import get_watcher
from deeplearning4j_tpu.zoo.bert import Bert

VOCAB = 43
MAXLEN = 32
BUCKETS = dict(batch_buckets=(1, 2, 4), prefill_buckets=(8, 16))

#: a 9-token shared "system prompt" (crosses two block_size=4 pages) plus
#: per-stream suffixes — the serving traffic shape the radix cache exists
#: for
SYSTEM = [5, 6, 7, 8, 9, 10, 11, 12, 13]
SHARED = [SYSTEM + [20, 21], SYSTEM + [22, 23, 24], SYSTEM + [25]]
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
def gen_prefix(target_net):
    return Generator(target_net, paged=True, block_size=4,
                     prefix_cache=True, **BUCKETS)


@pytest.fixture(scope="module")
def gen_both(target_net):
    return Generator(target_net, paged=True, block_size=4,
                     prefix_cache=True, prefill_chunk=8, **BUCKETS)


def _conserved(gen):
    ok, detail = gen.pool.conservation()
    assert ok, detail
    if gen.cache is not None:
        ok, detail = gen.cache.check()
        assert ok, detail


class TestPrefixIdentity:
    def test_cold_and_warm_identity(self, gen_prefix, gen_contiguous):
        """The acceptance bit: cold-cache (miss) AND warm-cache (shared
        blocks, resumed prefill) greedy decode == contiguous == O(T²)
        recompute, token-for-token."""
        ref = gen_contiguous.generate(SHARED, max_new_tokens=8)
        cold_stats, warm_stats = {}, {}
        cold = gen_prefix.generate(SHARED, max_new_tokens=8,
                                   stats=cold_stats)
        warm = gen_prefix.generate(SHARED, max_new_tokens=8,
                                   stats=warm_stats)
        assert cold == ref == warm
        assert warm == gen_contiguous.generate_full_recompute(
            SHARED, max_new_tokens=8)
        # warm run resumed past the shared full blocks
        assert warm_stats["prefix_hit_rate"] > 0
        assert any(p > 0 for p in warm_stats["resumed_positions"])
        _conserved(gen_prefix)

    def test_sampled_identity_stream_exact(self, gen_prefix,
                                           gen_contiguous):
        """temperature>0 on a WARM cache: resumed prefill consumes the
        same key stream, so sampled output is identical too."""
        gen_prefix.generate(SHARED, max_new_tokens=4)  # warm the trie
        key = jax.random.PRNGKey(11)
        a = gen_prefix.generate(SHARED, max_new_tokens=6, temperature=0.7,
                                key=key)
        b = gen_contiguous.generate(SHARED, max_new_tokens=6,
                                    temperature=0.7, key=key)
        assert a == b
        _conserved(gen_prefix)

    def test_cow_split_on_block_aligned_hit(self, gen_prefix,
                                            gen_contiguous):
        """A prompt that is EXACTLY full blocks fully hits the trie; the
        last block must be COW-split (decode writes into it) — identity
        preserved, split counted, nothing double-freed."""
        prompt = [[3, 4, 5, 6, 7, 8, 9, 10]]  # 8 = 2 whole blocks
        ref = gen_contiguous.generate(prompt, max_new_tokens=6)
        before = tm.get_telemetry().counter_total(
            "serving.prefix_cow_splits_total")
        first = gen_prefix.generate(prompt, max_new_tokens=6)
        second = gen_prefix.generate(prompt, max_new_tokens=6)  # COW here
        after = tm.get_telemetry().counter_total(
            "serving.prefix_cow_splits_total")
        assert first == ref == second
        assert after > before
        _conserved(gen_prefix)

    def test_eos_early_exit_decrefs_shared_blocks(self, gen_prefix,
                                                  gen_contiguous):
        """The satellite bugfix: eos early-exit on a stream whose prefix
        blocks are SHARED with the trie must decref, not free — the trie
        keeps serving the prefix afterwards, conservation intact."""
        gen_prefix.generate(SHARED, max_new_tokens=8)  # warm
        ref = gen_contiguous.generate([SHARED[0]], max_new_tokens=8)
        eos = ref[0][2]
        out = gen_prefix.generate([SHARED[0]], max_new_tokens=8,
                                  eos_id=eos)
        assert out[0] == ref[0][:ref[0].index(eos) + 1]
        _conserved(gen_prefix)
        # the prefix is still cached and still correct
        warm = gen_prefix.generate(SHARED, max_new_tokens=8,
                                   stats=(st := {}))
        assert warm == gen_contiguous.generate(SHARED, max_new_tokens=8)
        assert st["prefix_hit_rate"] > 0

    def test_speculative_identity(self, target_net, draft_net,
                                  gen_contiguous):
        """Speculative decode over a warm prefix cache: rollback of
        rejected draft tokens never touches shared blocks; output equals
        plain greedy cold AND warm."""
        gen = Generator(target_net, paged=True, block_size=4,
                        prefix_cache=True, draft_net=draft_net,
                        spec_tokens=3, **BUCKETS)
        ref = gen_contiguous.generate(SHARED, max_new_tokens=8)
        assert gen.generate(SHARED, max_new_tokens=8) == ref
        assert gen.generate(SHARED, max_new_tokens=8) == ref
        _conserved(gen)


class TestChunkedPrefill:
    def test_chunk_width_invariant(self, target_net, gen_contiguous):
        """Chunked prefill is pure mechanism: every window width yields
        the same tokens as the whole-prompt prefill."""
        ref = gen_contiguous.generate(RAGGED, max_new_tokens=6)
        gen = Generator(target_net, paged=True, block_size=4,
                        prefill_chunk=4, **BUCKETS)
        stats = {}
        out = gen.generate(RAGGED, max_new_tokens=6, stats=stats)
        assert out == ref
        assert stats["prefill_chunks"] >= 2  # 9-token prompt, 4-wide
        _conserved(gen)

    def test_chunked_plus_cache_identity(self, gen_both, gen_contiguous):
        """Both features together: chunked prefill resuming from a warm
        prefix — cold == warm == oracle."""
        long = [SYSTEM + list(range(14, 14 + 9)),
                SYSTEM + list(range(23, 23 + 7))]
        ref = gen_contiguous.generate(long, max_new_tokens=6)
        before = tm.get_telemetry().counter_total(
            "serving.chunked_prefill_chunks_total")
        cold = gen_both.generate(long, max_new_tokens=6)
        warm = gen_both.generate(long, max_new_tokens=6, stats=(st := {}))
        after = tm.get_telemetry().counter_total(
            "serving.chunked_prefill_chunks_total")
        assert cold == ref == warm
        assert st["prefix_hit_rate"] > 0
        assert after > before
        _conserved(gen_both)

    def test_zero_steady_state_recompiles_mixed_traffic(self, gen_both):
        """The compile-once substrate survives the new machinery: after
        warmup, mixed hit/miss/chunked/ragged traffic traces NOTHING."""
        gen_both.warmup()
        w = get_watcher()
        with w.scope() as s:
            gen_both.generate(SHARED, max_new_tokens=4)      # mixed hit
            gen_both.generate(SHARED, max_new_tokens=4)      # full hit
            gen_both.generate([[40, 41, 42]], max_new_tokens=4)  # miss
            gen_both.generate([SYSTEM + list(range(14, 30))],
                              max_new_tokens=4)              # chunked
            gen_both.generate(RAGGED, max_new_tokens=4)
        assert s.traces == 0, f"steady-state traced {s.traces}x"
        _conserved(gen_both)


class TestRefcountConservation:
    def test_property_random_interleavings(self, gen_prefix):
        """The satellite property test, on the accounting layer directly:
        hundreds of random (admit, share, COW-split, eos/finish,
        rollback, evict, pool-grow, exception-reset) interleavings, with
        pool conservation AND trie consistency asserted after EVERY op."""
        rng = random.Random(1234)
        net_blocks = gen_prefix.blocks
        pool = BlockPool(net_blocks, block_size=4, num_blocks=12,
                         max_length=MAXLEN)
        cache = PrefixCache(pool)
        prefixes = [tuple(SYSTEM), tuple(range(1, 9)), (30, 31, 32, 33)]
        active = []  # (table, pending_nodes)

        def check():
            ok, detail = pool.conservation()
            assert ok, detail
            ok, detail = cache.check()
            assert ok, detail

        def admit():
            base = list(rng.choice(prefixes))
            tokens = base + [rng.randrange(1, VOCAB)
                             for _ in range(rng.randrange(0, 4))]
            need = pool.blocks_needed(len(tokens), 4)
            with pool._lock:
                blocks, committed = cache.match(tokens)
                try:
                    table = blocks + pool.reserve(
                        [need - len(blocks)])[0]
                except Exception:
                    pool.decref(blocks)
                    return
                if committed and committed == len(tokens):
                    bi = (committed - 1) // pool.block_size
                    try:
                        table[bi] = pool.cow_split(table[bi])
                    except Exception:
                        pool.release([table])
                        return
                pending = cache.insert(tokens, table)
            active.append((table, pending))

        def finish():  # eos / normal completion: commit then release
            if not active:
                return
            table, pending = active.pop(rng.randrange(len(active)))
            cache.commit(pending)
            pool.release([table])

        def abort():  # exception path: rollback then release
            if not active:
                return
            table, pending = active.pop(rng.randrange(len(active)))
            cache.rollback(pending)
            pool.release([table])

        def evict():
            cache.evict(rng.randrange(1, 4))

        def grow():  # the _grow transaction: flush, rebind to a new pool
            nonlocal pool
            if active:  # live streams pin the old pool — as in Generator
                return
            cache.flush()
            pool = BlockPool(net_blocks, block_size=4,
                             num_blocks=pool.num_blocks + 4,
                             max_length=MAXLEN)
            cache.rebind(pool)

        def reset():  # the _reset_pools transaction
            while active:
                abort()
            cache.flush()

        ops = [admit, admit, admit, finish, finish, abort, evict, grow,
               reset]
        for _ in range(400):
            rng.choice(ops)()
            check()
        reset()
        check()
        assert pool.free_blocks() == pool.num_blocks

    def test_double_free_detected(self, target_net):
        gen = Generator(target_net, paged=True, block_size=4,
                        pool_blocks=8, **BUCKETS)
        (tbl,) = gen.pool.reserve([1])
        gen.pool.decref(tbl)
        with pytest.raises(ValueError, match="double-free"):
            gen.pool.decref(tbl)

    def test_pool_grow_flushes_and_rebinds_cache(self, target_net,
                                                 gen_contiguous):
        """Auto-pool growth under prefix caching: the trie is flushed,
        rebound to the grown pool, and keeps caching correctly after."""
        gen = Generator(target_net, paged=True, block_size=4,
                        prefix_cache=True, **BUCKETS)
        gen.pool = type(gen.pool)(gen.blocks, block_size=4, num_blocks=4,
                                  max_length=gen.max_length)
        gen.cache.rebind(gen.pool)
        assert gen._pool_auto
        ref = gen_contiguous.generate(SHARED, max_new_tokens=8)
        out = gen.generate(SHARED, max_new_tokens=8)  # needs > 4 blocks
        assert out == ref
        assert gen.pool.num_blocks > 4
        assert gen.generate(SHARED, max_new_tokens=8) == ref  # re-warms
        _conserved(gen)

    def test_exception_reset_clears_cache(self, gen_prefix,
                                          gen_contiguous):
        """_reset_pools (the exception path) flushes the trie and returns
        every block; the next request rebuilds the cache correctly."""
        gen_prefix.generate(SHARED, max_new_tokens=4)
        assert gen_prefix.cache.stats()["nodes"] > 0
        gen_prefix._reset_pools()
        assert gen_prefix.cache.stats()["nodes"] == 0
        assert gen_prefix.pool.free_blocks() == gen_prefix.pool.num_blocks
        _conserved(gen_prefix)
        assert gen_prefix.generate(SHARED, max_new_tokens=8) == \
            gen_contiguous.generate(SHARED, max_new_tokens=8)


class TestHealthProbe:
    def test_probe_asserts_conservation(self, gen_prefix):
        gen_prefix.generate(SHARED, max_new_tokens=4)
        assert gen_prefix.health_probe()

    def test_probe_catches_refcount_leak(self, target_net):
        """The satellite bugfix's tripwire: a manufactured refcount leak
        (block allocated but unreachable) flips the all-trash probe to
        unhealthy via the conservation check."""
        gen = Generator(target_net, paged=True, block_size=4,
                        pool_blocks=8, prefix_cache=True, **BUCKETS)
        assert gen.health_probe()
        leaked = gen.pool._free.pop()        # vanish a block: allocated
        gen.pool._ref[leaked] = 1            # by nobody, freed by nobody
        try:
            assert not gen.health_probe()
            ok, _ = tm.get_telemetry().health_report()
            assert not ok
        finally:
            del gen.pool._ref[leaked]
            gen.pool._free.append(leaked)
            assert gen.health_probe()


class TestObservability:
    def test_gauges_and_counters(self, gen_prefix):
        gen_prefix.generate(SHARED, max_new_tokens=4)
        gen_prefix.generate(SHARED, max_new_tokens=4)
        t = tm.get_telemetry()
        hits = t.gauge_values("serving.prefix_cache_hit_rate")
        assert hits and hits[-1] > 0
        assert t.gauge_values("serving.prefix_blocks_shared")

    def test_flight_recorder_and_spans_attribution(self, target_net):
        """Per-phase attribution rides the scheduler: flight records and
        trace spans carry prefix_hit_rate / resumed_position /
        prefill_chunks for warm chunked requests."""
        model = ServingModel(target_net, "prefix-m", kind="generate",
                             bucketing="batch=1,2;seq=8,16",
                             max_length=MAXLEN, block_size=4,
                             pool_blocks=64, prefix_cache=True,
                             prefill_chunk=8)
        model.warmup()
        sched = BatchScheduler(model, max_wait_ms=1.0)
        sched.start()
        try:
            prompt = np.asarray(SYSTEM + [20, 21], np.int32)
            sched.submit(prompt, max_new_tokens=4).result(timeout=60)
            fut = sched.submit(prompt, max_new_tokens=4)  # warm: hits
            fut.result(timeout=60)
            rec = sched.flight.dump(last=1)[0]
            assert rec["prefix_hit_rate"] > 0
            assert rec["resumed_position"] > 0
            assert rec["prefill_chunks"] >= 1
        finally:
            sched.shutdown()


class TestPrefixOverHttp:
    """Sessions sharing a system prompt as clients of the HTTP server: a
    prefix-cached, chunk-prefilled decoder over a PINNED pool, so that the
    429 contract stays testable under prefix sharing."""

    @pytest.fixture(scope="class")
    def served(self, target_net):
        from deeplearning4j_tpu.serving import ModelRouter, ModelServer

        router = ModelRouter(name="prefix-http")
        router.register(ServingModel(target_net, "prefix", kind="generate",
                                     bucketing="batch=1,2,4;seq=8,16",
                                     block_size=4, pool_blocks=24,
                                     prefix_cache=True, prefill_chunk=8),
                        max_wait_ms=1.0, queue_limit=64)
        server = ModelServer(router, port=0).start()
        yield server, router
        server.stop()

    def test_cold_and_warm_waves_identical_429_survives_sharing(
            self, served, gen_contiguous, http_json, all_at_once):
        server, router = served
        url = f"{server.url}/v1/models/prefix/generate"
        post = lambda obj: http_json(url, obj)  # noqa: E731
        ref = gen_contiguous.generate(SHARED, max_new_tokens=6)
        rec0 = tm.get_telemetry().counter_total("serving.recompiles_total")
        for wave in ("cold", "warm"):
            got = all_at_once(
                lambda p: post({"prompt_tokens": [p], "max_new_tokens": 6,
                                "lane": "batch"}), SHARED)
            assert [g[0] for g in got] == [200] * len(SHARED), wave
            assert [g[1]["tokens"][0] for g in got] == ref, wave
        assert tm.get_telemetry().counter_total(
            "serving.recompiles_total") == rec0
        text = http_json(f"{server.url}/metrics")[1]
        rates = [float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
                 if "serving_prefix_cache_hit_rate{" in line]
        assert any(v > 0 for v in rates), rates
        assert "serving_chunked_prefill_chunks_total" in text
        # one scheduler batch of the flood (4 streams x 7 blocks) needs 28
        # of the pinned 24, eviction included
        flood = [[(i + j) % (VOCAB - 1) + 1 for j in range(20)]
                 for i in range(8)]
        code, body, hdrs = post({"prompt_tokens": flood,
                                 "max_new_tokens": 8})
        assert code == 429 and body["error"] == "PoolExhaustedError"
        assert int(hdrs["Retry-After"]) >= 1
        code, body, _h = post({"prompt_tokens": SHARED[:2],
                               "max_new_tokens": 4})
        assert code == 200
        assert body["tokens"] == [r[:4] for r in ref[:2]]
        _conserved(router.get("prefix")[0].generator)

    def test_interactive_decodes_complete_beside_a_chunked_burst(
            self, served, http_json, all_at_once):
        """Long prompts (two chunks of 8) in the batch lane while short
        interactive requests arrive: every one of both is answered, and
        the burst's flight records say how many chunks each prefill took."""
        server, _router = served
        url = f"{server.url}/v1/models/prefix/generate"
        longs = [{"prompt_tokens": [SYSTEM + [30 + i] * 7],
                  "max_new_tokens": 6, "lane": "batch"} for i in range(6)]
        shorts = [{"prompt_tokens": [p], "max_new_tokens": 4}
                  for p in SHARED * 2]
        got = all_at_once(lambda obj: http_json(url, obj), longs + shorts)
        assert [g[0] for g in got] == [200] * 12
        recs = http_json(f"{server.url}/v1/models/prefix/debug/requests")[
            1]["requests"]
        assert any(r.get("prefill_chunks", 0) >= 2 for r in recs)
        assert any("prefix_hit_rate" in r for r in recs)
