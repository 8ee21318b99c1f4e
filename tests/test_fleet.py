"""Fleet front tier (serving/fleet.py, docs/SERVING.md#fleet).

Fast legs run against in-process STUB workers (stdlib HTTP servers with
canned behavior — no jax, no subprocesses): routing determinism and
rebalance bounds, header propagation across the proxy hop, failover /
502 / 503 contracts, rolling-reload ordering and version monotonicity,
metrics fan-in. The real-multi-process leg (archives → spawned
``fleet_worker`` processes → SIGKILL/reload under live HTTP) boots one
two-worker fleet for its class.
"""

import http.client
import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from deeplearning4j_tpu.serving.fleet import (FleetRouter, affinity_key,
                                              fleet_spec, rendezvous_pick,
                                              rendezvous_score)

# ------------------------------------------------------------ pure hashing


class TestRendezvous:
    def test_deterministic_and_order_independent(self):
        key = affinity_key("bert", [5, 9, 1, 3, 3, 7, 2, 8], 8)
        members = ["w0", "w1", "w2", "w3"]
        pick = rendezvous_pick(key, members)
        for _ in range(50):
            assert rendezvous_pick(key, members) == pick
        assert rendezvous_pick(key, list(reversed(members))) == pick
        assert rendezvous_pick(key, ["w2", "w0", "w3", "w1"]) == pick

    def test_spreads_across_workers(self):
        members = ["w0", "w1", "w2", "w3"]
        counts = {m: 0 for m in members}
        for i in range(200):
            key = affinity_key("m", [i, i + 1, i * 3, 7], 4)
            counts[rendezvous_pick(key, members)] += 1
        # blake2b-scored HRW over 200 distinct keys: every worker owns a
        # real share (the deterministic keys above give ~50 each)
        assert all(c >= 20 for c in counts.values()), counts

    def test_rebalance_moves_only_the_lost_workers_keys(self):
        members = ["w0", "w1", "w2", "w3"]
        keys = [affinity_key("m", [i, 2 * i + 1, 13], 3)
                for i in range(300)]
        before = {k: rendezvous_pick(k, members) for k in keys}
        survivors = [m for m in members if m != "w2"]
        for k in keys:
            after = rendezvous_pick(k, survivors)
            if before[k] != "w2":
                # the HRW minimal-disruption bound: a surviving worker's
                # keys NEVER move when another worker leaves the ring —
                # its radix caches stay warm through a peer's death
                assert after == before[k]

    def test_affinity_key_semantics(self):
        # only the HEAD participates: divergence past `head` shares a key
        a = affinity_key("m", [1, 2, 3, 4, 99, 98], 4)
        b = affinity_key("m", [1, 2, 3, 4, 50, 51, 52], 4)
        assert a == b
        assert affinity_key("m", [1, 2, 3, 9], 4) != a
        assert affinity_key("other", [1, 2, 3, 4], 4) != a  # model-scoped
        assert affinity_key("m", [1, 2, 3, 4], 0) is None  # affinity off
        assert affinity_key("m", [], 4) is None            # no prompt
        assert affinity_key("m", None, 4) is None

    def test_score_is_not_python_hash(self):
        # process-salted hash() would break cross-process agreement; the
        # blake2b score is a fixed function — pin one value
        assert rendezvous_score(b"key", "w0") == \
            rendezvous_score(b"key", "w0")
        assert isinstance(rendezvous_score(b"key", "w0"), int)


# ------------------------------------------------------------ stub workers


class _StubWorker:
    """A canned worker: healthz/models/metrics plus configurable POST
    behavior. ``kill_posts`` aborts the connection on data-plane POSTs
    (the transport-failure case the router must fail over); ``behavior``
    maps verb -> (status, body_dict, extra_headers)."""

    def __init__(self):
        self.kill_posts = False
        self.shed = False
        self.draining = False
        self.version = 1
        self.reload_calls = []
        self.post_log = []
        stub = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def handle_error(self, *a):  # quiet aborted connections
                pass

            def _send(self, status, obj, headers=()):
                body = json.dumps(obj).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in headers:
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    status = 503 if stub.draining else 200
                    self._send(status, {
                        "status": "ok",
                        "serving": {"draining": stub.draining}})
                elif self.path == "/v1/models":
                    self._send(200, {
                        "draining": stub.draining,
                        "models": {"m": {"version": stub.version,
                                         "queue_depth": 0,
                                         "prefix_hit_rate": 0.5}}})
                elif self.path == "/metrics":
                    self._send(200, {})  # overridden below
                else:
                    self._send(404, {"error": "no route"})

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n) if n else b"{}"
                if stub.kill_posts:
                    # transport failure: vanish without an HTTP response
                    self.connection.close()
                    raise ConnectionAbortedError
                rid = self.headers.get("X-Request-Id")
                stub.post_log.append((self.path, rid))
                if self.path.endswith("/reload"):
                    stub.version += 1
                    stub.reload_calls.append(
                        (time.monotonic(), json.loads(raw).get("path")))
                    self._send(200, {"model": "m",
                                     "version": stub.version})
                elif stub.shed:
                    # a worker-side 429: id + backoff hint must cross the
                    # router hop verbatim
                    self._send(429, {"error": "QueueFullError",
                                     "request_id": rid},
                               headers=[("Retry-After", "7"),
                                        ("X-Request-Id", rid or "")])
                else:
                    self._send(200, {"ok": True, "request_id": rid,
                                     "port": stub.port},
                               headers=[("X-Request-Id", rid or "")])

        # metrics needs text, not json — patch a real handler in
        def do_GET_metrics(handler):
            body = (b'# TYPE serving_queue_depth gauge\n'
                    b'serving_queue_depth{model="m"} 3\n'
                    b'up 1\n')
            handler.send_response(200)
            handler.send_header("Content-Type", "text/plain")
            handler.send_header("Content-Length", str(len(body)))
            handler.end_headers()
            handler.wfile.write(body)

        orig_get = Handler.do_GET

        def do_GET(handler):
            if handler.path == "/metrics":
                do_GET_metrics(handler)
            else:
                orig_get(handler)

        Handler.do_GET = do_GET
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}"
        # shutdown() waits out one poll interval: the default, half a
        # second, was most of every stub test's time
        self.thread = threading.Thread(
            target=self.httpd.serve_forever, kwargs={"poll_interval": 0.01},
            daemon=True)
        self.thread.start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self.thread.join(timeout=10)
        assert not self.thread.is_alive()


def _post(port, path, body=None, headers=None, timeout=10):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        raw = json.dumps(body or {}).encode()
        hdrs = {"Content-Type": "application/json"}
        hdrs.update(headers or {})
        conn.request("POST", path, body=raw, headers=hdrs)
        r = conn.getresponse()
        data = r.read()
        return r.status, json.loads(data) if data else {}, dict(r.getheaders())
    finally:
        conn.close()


def _get(port, path, timeout=10):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


@pytest.fixture
def stub_fleet():
    stubs = [_StubWorker(), _StubWorker()]
    fleet = FleetRouter(adopt=[s.url for s in stubs],
                        health_interval_s=0.1, affinity_head=4,
                        name="stubfleet").start()
    yield fleet, stubs
    fleet.stop()
    for s in stubs:
        s.stop()


class TestStubFleet:
    def test_proxies_and_propagates_request_id(self, stub_fleet):
        fleet, stubs = stub_fleet
        st, body, hdrs = _post(fleet.port, "/v1/models/m/infer",
                               {"inputs": [[1.0]]},
                               headers={"X-Request-Id": "caller-id-42"})
        assert st == 200
        # the caller's id crossed BOTH hops verbatim — never re-minted
        assert hdrs.get("X-Request-Id") == "caller-id-42"
        assert body["request_id"] == "caller-id-42"
        rids = [r for _p, r in stubs[0].post_log + stubs[1].post_log]
        assert rids == ["caller-id-42"]

    def test_mints_request_id_when_absent(self, stub_fleet):
        fleet, _stubs = stub_fleet
        st, _body, hdrs = _post(fleet.port, "/v1/models/m/infer", {})
        assert st == 200
        assert hdrs.get("X-Request-Id")  # minted at the front tier

    def test_retry_after_crosses_the_hop_verbatim(self, stub_fleet):
        fleet, stubs = stub_fleet
        for s in stubs:
            s.shed = True
        st, body, hdrs = _post(fleet.port, "/v1/models/m/infer", {},
                               headers={"X-Request-Id": "shed-1"})
        assert st == 429
        # the worker's backoff hint and the caller's id both survive the
        # router hop unmodified (the satellite bugfix contract)
        assert hdrs.get("Retry-After") == "7"
        assert hdrs.get("X-Request-Id") == "shed-1"

    def test_affinity_same_head_same_worker(self, stub_fleet):
        fleet, stubs = stub_fleet
        ports = set()
        for _ in range(6):
            st, body, _h = _post(
                fleet.port, "/v1/models/m/generate",
                {"prompt_tokens": [3, 1, 4, 1, 5, 9], "max_new_tokens": 2})
            assert st == 200
            ports.add(body["port"])
        assert len(ports) == 1  # every shared-head request: one worker
        assert fleet.status()["routing_decisions"]["affinity"] >= 6

    def test_failover_on_connection_failure(self, stub_fleet):
        fleet, stubs = stub_fleet
        # find which stub owns this prompt head, then break it
        st, body, _h = _post(fleet.port, "/v1/models/m/generate",
                             {"prompt_tokens": [2, 7, 1, 8]})
        owner = next(s for s in stubs if s.port == body["port"])
        owner.kill_posts = True
        st, body, _h = _post(fleet.port, "/v1/models/m/generate",
                             {"prompt_tokens": [2, 7, 1, 8]})
        assert st == 200  # failed over to the live worker
        assert body["port"] != owner.port
        assert fleet.status()["routing_decisions"]["failover"] >= 1

    def test_502_when_every_worker_fails_transport(self, stub_fleet):
        fleet, stubs = stub_fleet
        for s in stubs:
            s.kill_posts = True
        st, body, _h = _post(fleet.port, "/v1/models/m/infer", {})
        assert st == 502
        assert body["error"] == "WorkerProxyError"

    def test_503_with_retry_after_when_ring_empty(self, stub_fleet):
        fleet, stubs = stub_fleet
        for s in stubs:
            s.draining = True
        deadline = time.monotonic() + 5
        while fleet._ring() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not fleet._ring()
        st, body, hdrs = _post(fleet.port, "/v1/models/m/infer", {})
        assert st == 503
        assert body["error"] == "FleetUnavailableError"
        assert int(hdrs.get("Retry-After", 0)) >= 1
        st, _data = _get(fleet.port, "/healthz")
        assert st == 503  # fleet healthz follows the ring

    def test_draining_worker_leaves_ring_without_dropping_fleet(
            self, stub_fleet):
        fleet, stubs = stub_fleet
        stubs[0].draining = True
        deadline = time.monotonic() + 5
        while len(fleet._ring()) != 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert len(fleet._ring()) == 1
        st, body, _h = _post(fleet.port, "/v1/models/m/infer", {})
        assert st == 200
        assert body["port"] == stubs[1].port

    def test_rolling_reload_sequential_and_monotone(self, stub_fleet):
        fleet, stubs = stub_fleet
        st, body, _h = _post(fleet.port, "/v1/models/m/reload",
                             {"path": "/tmp/new.zip"})
        assert st == 200
        assert sorted(body["versions"]) == ["w0", "w1"]
        assert all(v == 2 for v in body["versions"].values())
        # worker-by-worker: the second worker's reload STARTED after the
        # first one's completed (timestamps recorded at response time)
        times = sorted(t for s in stubs for (t, _p) in s.reload_calls)
        assert len(times) == 2
        for s in stubs:
            assert s.reload_calls[0][1] == "/tmp/new.zip"
        # versions advance monotonically on a second roll
        st, body2, _h = _post(fleet.port, "/v1/models/m/reload",
                              {"path": "/tmp/new2.zip"})
        assert all(v == 3 for v in body2["versions"].values())

    def test_fleet_status_route(self, stub_fleet):
        fleet, stubs = stub_fleet
        st, data = _get(fleet.port, "/v1/fleet")
        assert st == 200
        doc = json.loads(data)
        assert doc["ring"] == ["w0", "w1"]
        assert doc["affinity_head"] == 4
        for wid in ("w0", "w1"):
            w = doc["workers"][wid]
            assert w["in_ring"] and w["healthy"] and w["adopted"]
            assert w["models"]["m"]["prefix_cache_hit_rate"] == 0.5

    def test_metrics_fan_in_relabels_per_worker(self, stub_fleet):
        fleet, _stubs = stub_fleet
        _post(fleet.port, "/v1/models/m/infer", {})  # one routed request
        st, data = _get(fleet.port, "/metrics")
        assert st == 200
        text = data.decode()
        # worker series re-exported with the worker label injected; bare
        # series get one minted
        assert 'serving_queue_depth{worker="w0",model="m"} 3' in text
        assert 'serving_queue_depth{worker="w1",model="m"} 3' in text
        assert 'up{worker="w0"} 1' in text
        # the router's own registry: routing decisions + ring gauges
        assert "serving_fleet_routing_decisions_total" in text
        assert 'serving_fleet_ring_size{fleet="stubfleet"} 2' in text
        # worker comment lines were stripped (one scrape = one parse)
        assert text.count("# TYPE serving_queue_depth gauge") == 0

    def test_404_route_contract(self, stub_fleet):
        fleet, _stubs = stub_fleet
        st, body, _h = _post(fleet.port, "/v1/models/m/nope", {})
        assert st == 404


# ------------------------------------------------------ real process leg


class TestRealFleet:
    """The tests/_dist_worker.py-style leg: real spawned worker processes,
    real HTTP, real SIGKILL. One fleet boot amortized across contracts:
    two workers, each serving a dense classifier and a prefix-cached
    causal BERT-tiny decoder from the archives a single-process oracle
    loads."""

    @pytest.fixture(scope="class")
    def fleet_env(self, tmp_path_factory):
        import numpy as np

        from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Adam
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer

        tmp = tmp_path_factory.mktemp("fleet")

        def dense(seed):
            conf = (NeuralNetConfiguration.builder().seed(seed)
                    .updater(Adam(1e-3)).batch_buckets((1, 2, 4)).list()
                    .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
                    .layer(OutputLayer(n_in=16, n_out=4, loss="mcxent",
                                       activation="softmax"))
                    .set_input_type(InputType.feed_forward(8)).build())
            return MultiLayerNetwork(conf).init()

        from deeplearning4j_tpu.zoo.bert import Bert

        net = dense(0)
        path = str(tmp / "clf.zip")
        ModelSerializer.write_model(net, path, save_updater=False)
        gen_net = Bert.tiny(causal=True, task="mlm", vocab_size=48,
                            max_length=32, hidden_dropout=0.0).init()
        gen_path = str(tmp / "gen.zip")
        ModelSerializer.write_model(gen_net, gen_path, save_updater=False)
        reg = {"max_wait_ms": 1.0, "queue_limit": 128}
        spec = fleet_spec(
            models=[{"id": "clf", "path": path, "kind": "classify",
                     "register": reg},
                    {"id": "gen", "path": gen_path, "kind": "generate",
                     "register": reg,
                     "model_kw": {"bucketing": {"batch_buckets": [1, 2, 4],
                                                "seq_buckets": [8]},
                                  "prefix_cache": True, "block_size": 4}}],
            env={"JAX_PLATFORMS": "cpu"})
        fleet = FleetRouter(spec, n_workers=2, affinity_head=8,
                            health_interval_s=0.2, name="testfleet").start()
        x = np.random.RandomState(3).normal(size=(2, 8)) \
            .astype(np.float32)
        yield {"fleet": fleet, "net": net, "x": x, "tmp": tmp,
               "dense": dense, "np": np, "gen_net": gen_net}
        fleet.stop()

    def test_http_identical_to_inprocess_oracle(self, fleet_env):
        fleet, net, x, np = (fleet_env["fleet"], fleet_env["net"],
                             fleet_env["x"], fleet_env["np"])
        oracle = np.asarray(net.output(x))
        for _ in range(4):
            st, body, hdrs = _post(fleet.port, "/v1/models/clf/infer",
                                   {"inputs": x.tolist()},
                                   headers={"X-Request-Id": "oracle-1"})
            assert st == 200
            assert hdrs.get("X-Request-Id") == "oracle-1"
            assert np.allclose(np.asarray(body["outputs"]), oracle,
                               atol=1e-5)

    def test_shared_prefixes_keep_their_worker_and_its_cache_warm(
            self, fleet_env):
        """Four groups of six prompts, each group sharing an 8-token head
        (two radix blocks): every answer is the single-process oracle's,
        token for token; affinity decides the routing; each worker that
        served a group reads a prefix hit rate no lower than one process
        serving all of it would; and a second, identical burst compiles
        nothing on any worker."""
        from deeplearning4j_tpu.serving import Generator

        fleet = fleet_env["fleet"]
        groups = []
        for g in range(4):
            head = [(7 * g + k) % 40 + 1 for k in range(8)]
            groups.append([head + [(g + 11 * t + j) % 40 + 1
                                   for j in range(4)] for t in range(6)])
        oracle = Generator(fleet_env["gen_net"], paged=True, block_size=4,
                           prefix_cache=True, batch_buckets=(1, 2, 4),
                           prefill_buckets=(8,))

        def burst():
            for grp in groups:
                for p in grp:
                    st, body, _h = _post(
                        fleet.port, "/v1/models/gen/generate",
                        {"prompt_tokens": p, "max_new_tokens": 4},
                        timeout=60)
                    assert st == 200
                    assert body["tokens"] == oracle.generate(
                        [p], max_new_tokens=4)

        def scrape(port, name, **labels):
            """Every value of the series ``name`` carrying ``labels``."""
            _st, data = _get(port, "/metrics")
            return [float(line.rsplit(" ", 1)[1])
                    for line in data.decode().splitlines()
                    if line.startswith("dl4j_" + name)
                    and all(f'{k}="{v}"' in line
                            for k, v in labels.items())]

        burst()
        compiles = {w.worker_id: scrape(w.port,
                                        "xla_backend_compiles_total")
                    for w in fleet.workers}
        burst()
        assert compiles == {w.worker_id: scrape(
            w.port, "xla_backend_compiles_total") for w in fleet.workers}
        assert sum(scrape(fleet.port,
                          "serving_fleet_routing_decisions_total",
                          reason="affinity")) > 0
        rates = [r for w in fleet.workers
                 for r in scrape(fleet.port, "serving_prefix_cache_hit_rate",
                                 worker=w.worker_id, model="gen")]
        assert rates and all(r > 0 for r in rates)
        assert max(rates) >= oracle.prefix_hit_rate() - 1e-6

    def test_sigkill_failover_and_respawn(self, fleet_env):
        fleet, x = fleet_env["fleet"], fleet_env["x"]
        victim = fleet._ring()[0]
        os.kill(victim.pid, 9)
        ok = 0
        for _ in range(8):
            st, _body, _h = _post(fleet.port, "/v1/models/clf/infer",
                                  {"inputs": x.tolist()}, timeout=30)
            ok += st == 200
        assert ok == 8  # zero loss: requests failed over mid-kill
        deadline = time.monotonic() + 120
        while len(fleet._ring()) < 2 and time.monotonic() < deadline:
            time.sleep(0.25)
        assert len(fleet._ring()) == 2  # respawned + re-entered the ring
        assert fleet.worker(victim.worker_id).restarts >= 1

    def test_rolling_reload_under_live_traffic(self, fleet_env):
        fleet, x, np = fleet_env["fleet"], fleet_env["x"], fleet_env["np"]
        net2 = fleet_env["dense"](7)
        path2 = str(fleet_env["tmp"] / "clf2.zip")
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer

        ModelSerializer.write_model(net2, path2, save_updater=False)
        stop = threading.Event()
        failures = []

        def traffic():
            while not stop.is_set():
                st, _b, _h = _post(fleet.port, "/v1/models/clf/infer",
                                   {"inputs": x.tolist()}, timeout=30)
                if st != 200:
                    failures.append(st)

        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        try:
            st, body, _h = _post(fleet.port, "/v1/models/clf/reload",
                                 {"path": path2}, timeout=300)
        finally:
            stop.set()
            t.join(timeout=30)
        assert st == 200
        versions = body["versions"]
        assert sorted(versions) == ["w0", "w1"]
        assert all(v >= 2 for v in versions.values())
        assert not failures  # zero fleet-level shed during the roll
        st, body, _h = _post(fleet.port, "/v1/models/clf/infer",
                             {"inputs": x.tolist()}, timeout=30)
        oracle2 = np.asarray(net2.output(x))
        assert np.allclose(np.asarray(body["outputs"]), oracle2,
                           atol=1e-5)
