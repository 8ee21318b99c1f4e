"""Serving-tier correctness (ISSUE 8): batched-vs-sequential bit-identity
under ragged coalescing, KV-cache decode == full-recompute decode (exact
for greedy), deadline-miss shedding through the 429 path, multi-model
isolation, bucket-policy single source of truth (pad-up-not-retrace with
``serving.recompiles_total`` == 0 in steady state), and graceful drain on
a REAL SIGTERM reusing the r11 seam."""

import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.data.bucketing import BucketingPolicy
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.serving import (DeadlineExceededError, Generator,
                                        ModelRouter, ModelServer,
                                        QueueFullError, ServingModel)
from deeplearning4j_tpu.util import telemetry as tm
from deeplearning4j_tpu.util.compile_watcher import get_watcher
from deeplearning4j_tpu.zoo.bert import Bert

R = np.random.default_rng(7)


def _dense_net(buckets=(2, 4, 8), n_in=10, n_out=4, seed=0):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .batch_buckets(buckets).list()
            .layer(DenseLayer(n_in=n_in, n_out=24, activation="relu"))
            .layer(OutputLayer(n_in=24, n_out=n_out, loss="mcxent",
                               activation="softmax"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _decoder_net(vocab=43, max_length=32, seed=0):
    return Bert.tiny(causal=True, task="mlm", vocab_size=vocab,
                     max_length=max_length, hidden_dropout=0.0).init()


def _counter(name: str) -> float:
    tele = tm.get_telemetry()
    return sum(v for (n, _l), v in tele.counters.items() if n == name)


@pytest.fixture
def dense_model():
    net = _dense_net()
    model = ServingModel(net, "dense")
    model.warmup()
    return net, model


class TestBatchedBitIdentity:
    def test_ragged_coalescing_bit_identical(self, dense_model):
        """Three ragged requests (3+5+2 rows) coalesced into one bucketed
        batch must return EXACTLY what each request gets alone — the r8
        0-pad contract carried through the scheduler."""
        net, model = dense_model
        from deeplearning4j_tpu.serving.scheduler import BatchScheduler

        sizes = (3, 5, 2)
        xs = [R.normal(size=(n, 10)).astype(np.float32) for n in sizes]
        sched = BatchScheduler(model, max_wait_ms=50.0)
        futs = [sched.submit(x) for x in xs]  # queued before the worker
        sched.start()                          # starts -> ONE coalesced batch
        got = [np.asarray(f.result(timeout=30)) for f in futs]
        sched.drain(timeout=10)
        assert sched.counts["completed"] == 3
        for x, g in zip(xs, got):
            assert np.array_equal(g, np.asarray(net.output(x)))

    def test_direct_execute_matches_sequential(self, dense_model):
        net, model = dense_model
        xs = [R.normal(size=(n, 10)).astype(np.float32) for n in (1, 4, 6)]
        batched, stats = model.execute(xs)
        assert stats["real_rows"] == 11
        for x, g in zip(xs, batched):
            assert np.array_equal(np.asarray(g), np.asarray(net.output(x)))

    def test_generate_coalesced_matches_sequential(self):
        net = _decoder_net()
        model = ServingModel(net, "dec", kind="generate",
                             bucketing=BucketingPolicy(
                                 batch_buckets=(1, 2, 4), seq_buckets=(8,)))
        model.warmup()
        prompts = [np.asarray(p, np.int32)
                   for p in ([1, 2, 3], [4, 5, 6, 7], [8, 9])]
        both, _ = model.execute(prompts, max_new_tokens=5)
        solo = [model.execute([p], max_new_tokens=5)[0][0] for p in prompts]
        assert list(both) == list(solo)


class TestKvCacheDecode:
    def test_greedy_cache_equals_full_recompute(self):
        """The acceptance bit: KV-cache decode == full-recompute decode,
        exact token-for-token under greedy."""
        net = _decoder_net()
        gen = Generator(net, batch_buckets=(1, 2, 4), prefill_buckets=(8, 16))
        prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9]]
        cached = gen.generate(prompts, max_new_tokens=8)
        recomputed = gen.generate_full_recompute(prompts, max_new_tokens=8)
        assert cached == recomputed
        assert all(len(r) == 8 for r in cached)

    def test_prompt_between_prefill_buckets_pads_up(self):
        net = _decoder_net()
        gen = Generator(net, batch_buckets=(1, 2), prefill_buckets=(4, 8))
        gen.warmup()
        w = get_watcher()
        with w.scope() as s:
            gen.generate([[1, 2, 3, 4, 5, 6]], max_new_tokens=3)  # len 6 -> 8
            assert s.traces == 0

    def test_prompt_above_largest_prefill_bucket_uses_max_length(self):
        """A prompt longer than the largest explicit prefill bucket pads
        up to max_length (the implicit final bucket warmup also primes)
        instead of tracing a fresh per-length executable."""
        net = _decoder_net(max_length=32)
        gen = Generator(net, batch_buckets=(1, 2), prefill_buckets=(8,))
        assert gen._prefill_len(6) == 8
        assert gen._prefill_len(13) == 32   # above bucket 8 -> max_length
        gen.warmup()  # primes 8 AND 32
        w = get_watcher()
        with w.scope() as s:
            for n in (9, 13, 20):  # distinct above-bucket lengths
                gen.generate([list(range(1, n + 1))], max_new_tokens=2)
            assert s.traces == 0
        # cached decode still equals recompute at the max_length shape
        prompts = [list(range(1, 14))]
        assert gen.generate(prompts, max_new_tokens=4) == \
            gen.generate_full_recompute(prompts, max_new_tokens=4)

    def test_decode_compile_once(self):
        net = _decoder_net()
        gen = Generator(net, batch_buckets=(1, 2), prefill_buckets=(8,))
        gen.generate([[1, 2, 3]], max_new_tokens=4)  # traces prefill+decode
        w = get_watcher()
        with w.scope() as s:
            gen.generate([[5, 6]], max_new_tokens=6)   # same buckets
            gen.generate([[7, 8, 9, 1]], max_new_tokens=3)
            assert s.traces == 0

    def test_temperature_sampling_deterministic_per_key(self):
        import jax

        net = _decoder_net()
        gen = Generator(net, batch_buckets=(1, 2), prefill_buckets=(8,))
        a = gen.generate([[1, 2, 3]], max_new_tokens=6, temperature=0.8,
                         key=jax.random.PRNGKey(3))
        b = gen.generate([[1, 2, 3]], max_new_tokens=6, temperature=0.8,
                         key=jax.random.PRNGKey(3))
        assert a == b
        toks = a[0]
        assert all(0 <= t < 43 for t in toks)

    def test_eos_trimming(self):
        net = _decoder_net()
        gen = Generator(net, batch_buckets=(1, 2), prefill_buckets=(8,))
        full = gen.generate([[1, 2, 3]], max_new_tokens=8)[0]
        eos = full[2]
        trimmed = gen.generate([[1, 2, 3]], max_new_tokens=8,
                               eos_id=eos)[0]
        assert trimmed == full[: full.index(eos) + 1]

    def test_rejects_non_causal(self):
        net = Bert.tiny(task="mlm", vocab_size=31, max_length=16,
                        hidden_dropout=0.0).init()  # bidirectional
        with pytest.raises(ValueError, match="causal"):
            Generator(net)


class TestBucketSourceOfTruth:
    def test_between_buckets_pads_up_no_retrace(self, dense_model):
        """A request size that falls between buckets pads up to the next
        bucket instead of tracing a new program; serving.recompiles_total
        stays 0 in steady state."""
        net, model = dense_model
        rec_before = _counter("serving.recompiles_total")
        w = get_watcher()
        with w.scope() as s:
            for n in (1, 3, 5, 7, 8):  # between-bucket + exact sizes
                results, stats = model.execute(
                    [R.normal(size=(n, 10)).astype(np.float32)])
                assert stats["recompiles"] == 0
            assert s.traces == 0
        from deeplearning4j_tpu.serving.scheduler import BatchScheduler

        sched = BatchScheduler(model).start()
        sched.submit(R.normal(size=(3, 10)).astype(np.float32)
                     ).result(timeout=30)
        sched.drain(timeout=10)
        assert _counter("serving.recompiles_total") == rec_before

    def test_above_largest_bucket_chunks_no_retrace(self, dense_model):
        net, model = dense_model
        w = get_watcher()
        with w.scope() as s:
            x = R.normal(size=(21, 10)).astype(np.float32)  # > bucket 8
            results, stats = model.execute([x])
            assert s.traces == 0
        assert np.array_equal(np.asarray(results[0]),
                              np.asarray(net.output(x)))
        # 21 -> 8 + 8 + 5(->8): the plan never leaves the bucket set
        assert model.policy.plan_serving_batch(21) == [(8, 8), (8, 8),
                                                       (5, 8)]

    def test_plan_cap_bounds_padded_batch(self):
        """batch_limit caps the PADDED per-call batch (device memory):
        chunking targets the largest bucket under the cap; when no bucket
        fits the cap wins and chunks pass through unpadded."""
        pol = BucketingPolicy(batch_buckets=(2, 4, 8))
        assert pol.plan_serving_batch(6, cap=6) == [(4, 4), (2, 2)]
        assert all(p <= 6 for _t, p in pol.plan_serving_batch(23, cap=6))
        assert pol.plan_serving_batch(3, cap=1) == [(1, 1)] * 3  # no fit
        pow2 = BucketingPolicy(batch_buckets="pow2")
        assert all(p <= 12 for _t, p in pow2.plan_serving_batch(30, cap=12))

    def test_parallel_inference_shares_plan(self):
        """ParallelInference.output rides the same plan: an above-bucket
        request chunks to the largest bucket instead of tracing a fresh
        signature (the satellite fix in parallel/wrapper.py)."""
        from deeplearning4j_tpu.parallel.wrapper import ParallelInference

        net = _dense_net()
        policy = BucketingPolicy(batch_buckets=(2, 4, 8))
        pi = ParallelInference(net, bucketing=policy)
        pi.warmup(batch_sizes=policy.batch_buckets, input_shape=(10,))
        w = get_watcher()
        with w.scope() as s:
            x = R.normal(size=(19, 10)).astype(np.float32)
            out = pi.output(x)
            assert s.traces == 0
        assert out.shape == (19, 4)

    def test_batch_limit_bounds_padded_device_batch(self):
        """batch_limit is a device-memory bound: when it excludes every
        bucket, chunks pass through unpadded at the cap — the forward must
        never see a batch larger than batch_limit."""
        import jax

        from deeplearning4j_tpu.parallel.mesh import TrainingMesh
        from deeplearning4j_tpu.parallel.wrapper import ParallelInference

        net = _dense_net()
        # 1-device mesh: mesh divisibility adds its own floor (>= one row
        # per device), which is the orthogonal constraint — the cap
        # contract is about bucketing rounding past batch_limit
        pi = ParallelInference(
            net, mesh=TrainingMesh(data=1, devices=jax.devices()[:1]),
            bucketing=BucketingPolicy(batch_buckets=(8, 16)),
            batch_limit=4)
        seen = []
        orig = pi._fwd
        pi._fwd = lambda p, s, x: (seen.append(x.shape), orig(p, s, x))[1]
        x = R.normal(size=(10, 10)).astype(np.float32)
        out = pi.output(x)
        assert out.shape == (10, 4)
        assert seen and all(sh[0] <= 4 for sh in seen), seen

    def test_router_load_generate_without_seq_buckets_boots(self, tmp_path):
        """router.load(kind='generate') on an archive whose conf has no
        seq_buckets (the common case) must warm on the pow2 fallback, not
        crash the server boot."""
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer

        net = _decoder_net(max_length=16)
        path = str(tmp_path / "decoder.zip")
        ModelSerializer.write_model(net, path)
        router = ModelRouter(name="genload")
        router.load("g", path, kind="generate")
        assert router.warmup() > 0
        fut = router.submit("g", np.asarray([1, 2, 3], np.int32),
                            lane="batch", max_new_tokens=3)
        assert len(fut.result(timeout=60)) == 3
        router.shutdown()

    def test_warmup_and_scheduler_one_policy_object(self, dense_model):
        _net, model = dense_model
        from deeplearning4j_tpu.serving.scheduler import BatchScheduler

        sched = BatchScheduler(model)
        assert sched.max_batch == model.policy.largest_batch_bucket()
        if model.inference is not None:
            assert model.inference.bucketing is model.policy


class TestSheddingAndIsolation:
    def test_deadline_miss_sheds(self, dense_model):
        _net, model = dense_model
        from deeplearning4j_tpu.serving.scheduler import BatchScheduler

        before = _counter("serving.shed_total")
        sched = BatchScheduler(model, max_wait_ms=1.0)
        fut = sched.submit(R.normal(size=(2, 10)).astype(np.float32),
                           deadline_ms=-1)  # already expired
        sched.start()
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=30)
        sched.drain(timeout=10)
        assert _counter("serving.shed_total") > before

    def test_queue_full_admission_control(self, dense_model):
        _net, model = dense_model
        from deeplearning4j_tpu.serving.scheduler import BatchScheduler

        sched = BatchScheduler(model, queue_limit=2)  # worker NOT started
        x = R.normal(size=(1, 10)).astype(np.float32)
        sched.submit(x)
        sched.submit(x)
        with pytest.raises(QueueFullError):
            sched.submit(x)
        sched.shutdown()

    def test_multi_model_isolation(self):
        """One model's flood must not starve another model's priority
        lane: per-model schedulers make isolation structural."""
        slow_net = _decoder_net()
        fast_net = _dense_net()
        router = ModelRouter(name="iso")
        slow = ServingModel(slow_net, "slow", kind="generate",
                            bucketing=BucketingPolicy(
                                batch_buckets=(1,), seq_buckets=(8,)))
        fast = ServingModel(fast_net, "fast")
        router.register(slow, max_wait_ms=0.5, queue_limit=64)
        router.register(fast, max_wait_ms=0.5, queue_limit=64)
        router.warmup()
        flood = [router.submit(
            "slow", np.asarray([1, 2, 3], np.int32), lane="batch",
            max_new_tokens=12) for _ in range(8)]
        fut = router.submit("fast",
                            R.normal(size=(2, 10)).astype(np.float32))
        fut.result(timeout=30)  # completes while the flood is queued
        _m, slow_sched = router.get("slow")
        assert slow_sched.queue_depth() > 0, \
            "flood drained before the fast request — load too light to " \
            "prove isolation"
        for f in flood:
            f.result(timeout=120)
        router.shutdown()

    def test_interactive_lane_beats_batch_lane(self, dense_model):
        """Within one model, the interactive lane drains before queued
        batch-lane work."""
        _net, model = dense_model
        from deeplearning4j_tpu.serving.scheduler import BatchScheduler

        sched = BatchScheduler(model, max_wait_ms=0.0)
        x = R.normal(size=(2, 10)).astype(np.float32)
        order = []
        batch_futs = [sched.submit(x, lane="batch") for _ in range(4)]
        inter = sched.submit(x, lane="interactive")
        for i, f in enumerate(batch_futs):
            f.add_done_callback(lambda _f, i=i: order.append(("b", i)))
        inter.add_done_callback(lambda _f: order.append(("i", 0)))
        sched.start()
        inter.result(timeout=30)
        for f in batch_futs:
            f.result(timeout=30)
        sched.drain(timeout=10)
        assert order[0] == ("i", 0), order


class TestRouterAndSerializer:
    def test_load_from_model_serializer(self, tmp_path):
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer

        net = _dense_net(seed=5)
        path = str(tmp_path / "dense.zip")
        ModelSerializer.write_model(net, path)
        meta = ModelSerializer.peek_meta(path)
        assert meta["type"] == "MultiLayerNetwork"
        router = ModelRouter(name="loadtest")
        router.load("restored", path,
                    bucketing=BucketingPolicy(batch_buckets=(2, 4)))
        model, _sched = router.get("restored")
        model.warmup()
        x = R.normal(size=(3, 10)).astype(np.float32)
        fut = router.submit("restored", x)
        assert np.array_equal(np.asarray(fut.result(timeout=30)),
                              np.asarray(net.output(x)))
        router.shutdown()

    def test_unknown_model_raises(self):
        from deeplearning4j_tpu.serving import UnknownModelError

        router = ModelRouter(name="empty")
        with pytest.raises(UnknownModelError):
            router.submit("ghost", np.zeros((1, 4), np.float32))

    def test_status_lists_models(self, dense_model):
        _net, model = dense_model
        router = ModelRouter(name="status")
        router.register(model)
        st = router.status()
        assert "dense" in st["models"]
        assert st["models"]["dense"]["kind"] == "classify"
        router.shutdown()


def _post(url, obj, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read())
        except Exception:
            return e.code, {}


class TestHttpRequestScope:
    """What a caller sees on the wire beside the payload: the shed
    contract's headers, the request id on every answer, and the
    per-model debug routes. One dense server for the class."""

    @pytest.fixture(scope="class")
    def dense_server(self):
        net = _dense_net()
        router = ModelRouter(name="http-scope")
        router.register(ServingModel(net, "dense"), max_wait_ms=1.0)
        server = ModelServer(router, port=0).start()
        yield server
        server.stop()

    X = R.normal(size=(3, 10)).astype(np.float32).tolist()

    def test_request_id_echoed_on_200_and_minted_when_absent(
            self, dense_server, http_json):
        url = f"{dense_server.url}/v1/models/dense/infer"
        code, body, hdrs = http_json(url, {"inputs": self.X},
                                     request_id="rid-ok")
        assert code == 200
        assert hdrs.get("X-Request-Id") == body["request_id"] == "rid-ok"
        code, body, hdrs = http_json(url, {"inputs": self.X})
        assert code == 200 and hdrs.get("X-Request-Id")
        assert body["request_id"] == hdrs["X-Request-Id"]

    def test_shed_answers_429_with_retry_after_and_the_callers_id(
            self, dense_server, http_json):
        code, body, hdrs = http_json(
            f"{dense_server.url}/v1/models/dense/infer",
            {"inputs": self.X, "deadline_ms": -1}, request_id="rid-shed")
        assert code == 429 and body["error"] == "DeadlineExceededError"
        assert int(hdrs["Retry-After"]) >= 1
        assert hdrs.get("X-Request-Id") == body["request_id"] == "rid-shed"
        # the flight recorder's dump for the model holds the shed with its
        # cause, and the served requests with their phase timings
        http_json(f"{dense_server.url}/v1/models/dense/infer",
                  {"inputs": self.X})
        code, dump, _h = http_json(
            f"{dense_server.url}/v1/models/dense/debug/requests?last=64")
        recs = dump["requests"]
        assert code == 200
        assert any(r["id"] == "rid-shed" and r["status"] == "shed"
                   and r["cause"] == "deadline" for r in recs)
        assert any(r["status"] == "ok" and r["compute_ms"] is not None
                   and r["total_ms"] >= r["compute_ms"] for r in recs)

    @pytest.mark.parametrize("method,path", [
        ("POST", "/v1/models/ghost/infer"),
        ("GET", "/v1/models/ghost/debug/requests"),
        ("GET", "/v1/nothing"),
    ])
    def test_unknown_model_or_route_answers_404(self, dense_server,
                                                http_json, method, path):
        body = {"inputs": self.X} if method == "POST" else None
        assert http_json(dense_server.url + path, body)[0] == 404

    def test_slo_route_serves_burn_rate_windows(self, dense_server,
                                                http_json):
        from deeplearning4j_tpu.util import slo

        slo.register(slo.SloObjective("http-avail", "availability",
                                      target=0.5, model="dense"))
        try:
            http_json(f"{dense_server.url}/v1/models/dense/infer",
                      {"inputs": self.X})
            code, doc, _h = http_json(f"{dense_server.url}/slo")
            objs = {o["name"]: o for o in doc["objectives"]}
            assert code == 200
            assert "burn_rate" in objs["http-avail"]["windows"]["60s"]
            assert objs["http-avail"]["compliant"] is True
            _code, text, _h = http_json(f"{dense_server.url}/metrics")
            assert 'dl4j_slo_burn_rate{slo="http-avail"' in text
            assert "serving_queue_depth" in text
            assert "serving_request_latency_seconds" in text
        finally:
            slo.reset()


class TestHttpServer:
    def test_infer_shed_and_drain_on_sigterm(self):
        """The HTTP contract end-to-end: 200 with bit-identical outputs,
        deterministic 429 on an expired deadline, then a REAL SIGTERM
        drains gracefully (finish queued work, 503 afterwards) — the r11
        drain seam on the serving side."""
        net = _dense_net()
        router = ModelRouter(name="http")
        router.register(ServingModel(net, "dense"), max_wait_ms=1.0)
        server = ModelServer(router, port=0).start()
        try:
            x = R.normal(size=(3, 10)).astype(np.float32)
            code, body = _post(f"{server.url}/v1/models/dense/infer",
                               {"inputs": x.tolist()})
            assert code == 200
            pad = np.concatenate([x, np.zeros((1, 10), np.float32)])
            assert np.array_equal(
                np.asarray(body["outputs"], np.float32),
                np.asarray(net.output(pad))[:3].astype(np.float32))

            code, body = _post(f"{server.url}/v1/models/dense/infer",
                               {"inputs": x.tolist(), "deadline_ms": -1})
            assert code == 429
            assert body["error"] == "DeadlineExceededError"

            drains_before = _counter("serving.drains_total")
            os.kill(os.getpid(), signal.SIGTERM)
            assert server.wait_drained(timeout=30)
            assert _counter("serving.drains_total") == drains_before + 1
            code, _ = _post(f"{server.url}/v1/models/dense/infer",
                            {"inputs": x.tolist()})
            assert code == 503
            ok, checks = tm.get_telemetry().health_report()
            assert checks["serving.drained"]["ok"]
        finally:
            server.stop()

    def test_generate_route_and_healthz_section(self):
        net = _decoder_net()
        router = ModelRouter(name="http-gen")
        model = ServingModel(net, "dec", kind="generate",
                             bucketing=BucketingPolicy(
                                 batch_buckets=(1, 2), seq_buckets=(8,)))
        router.register(model, max_wait_ms=1.0)
        server = ModelServer(router, port=0).start()
        try:
            code, body = _post(
                f"{server.url}/v1/models/dec/generate",
                {"prompt_tokens": [1, 2, 3], "max_new_tokens": 4})
            assert code == 200
            gen_direct = model.generator.generate([[1, 2, 3]],
                                                  max_new_tokens=4)
            assert body["tokens"] == gen_direct

            r = urllib.request.urlopen(f"{server.url}/healthz", timeout=30)
            health = json.loads(r.read())
            assert "dec" in health["serving"]["models"]
            r = urllib.request.urlopen(f"{server.url}/metrics", timeout=30)
            text = r.read().decode()
            assert "serving_requests_total" in text
            assert "serving_recompiles_total" in text
        finally:
            server.stop()

    def test_drain_in_flight_requests_complete(self):
        """Queued work submitted before the drain signal completes (finish
        in-flight, the elastic contract)."""
        net = _dense_net()
        router = ModelRouter(name="drain2")
        sm = ServingModel(net, "dense")
        sm.warmup()
        from deeplearning4j_tpu.serving.scheduler import BatchScheduler

        sched = BatchScheduler(sm, max_wait_ms=5.0)
        xs = [R.normal(size=(2, 10)).astype(np.float32) for _ in range(5)]
        futs = [sched.submit(x) for x in xs]   # queued, worker not running
        sched.start()
        assert sched.drain(timeout=30)         # must FINISH, not fail them
        for x, f in zip(xs, futs):
            assert np.array_equal(np.asarray(f.result(timeout=1)),
                                  np.asarray(net.output(x)))
