"""Compile-once execution subsystem (docs/COMPILE_CACHE.md): shape
bucketing, recompile-count regression, bit-identity of bucketed vs unpadded
execution, AOT warmup, the persistent compilation cache, the SameDiff
cross-instance executable cache, and recompile observability."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.data import ArrayDataSetIterator, BucketingPolicy
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.computation_graph import (
    ComputationGraph, ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.recurrent import LSTM, RnnOutputLayer
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.util import get_watcher

R = np.random.default_rng(42)


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(bool((x == y).all()) for x, y in zip(la, lb))


def _mlp(seed=7, buckets=None, seq=None, tbptt=0, recurrent=False):
    b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
    if buckets is not None:
        b = b.batch_buckets(buckets)
    if seq is not None:
        b = b.seq_buckets(seq)
    if tbptt:
        b = b.tbptt_length(tbptt)
    lb = b.list()
    if recurrent:
        conf = (lb.layer(LSTM(n_in=6, n_out=8))
                .layer(RnnOutputLayer(n_in=8, n_out=3))
                .set_input_type(InputType.recurrent(6, 12)).build())
    else:
        conf = (lb.layer(DenseLayer(n_in=12, n_out=16, activation="tanh"))
                .layer(OutputLayer(n_in=16, n_out=5))
                .set_input_type(InputType.feed_forward(12)).build())
    return MultiLayerNetwork(conf).init()


def _cg(seed=3, buckets=None):
    b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
    if buckets is not None:
        b = b.batch_buckets(buckets)
    g = (b.graph_builder().add_inputs("in")
         .add_layer("d1", DenseLayer(n_in=10, n_out=14, activation="tanh"),
                    "in")
         .add_layer("d2", DenseLayer(n_in=10, n_out=14, activation="relu"),
                    "in")
         .add_layer("out", OutputLayer(n_in=28, n_out=4), "d1", "d2")
         .set_outputs("out").set_input_types((10,)).build())
    return ComputationGraph(g).init()


def _dense_data(n=21, f=12, c=5, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = np.eye(c, dtype=np.float32)[rng.integers(0, c, n)]
    return x, y


# ---------------------------------------------------------------------------
# BucketingPolicy unit behavior
# ---------------------------------------------------------------------------
class TestBucketingPolicy:
    def test_pow2_rounding(self):
        p = BucketingPolicy(batch_buckets="pow2")
        assert [p.bucket_batch(n) for n in (1, 2, 3, 5, 8, 9, 33)] == \
            [1, 2, 4, 8, 8, 16, 64]

    def test_explicit_rounding_and_passthrough(self):
        p = BucketingPolicy(batch_buckets=(8, 16, 32))
        assert p.bucket_batch(5) == 8
        assert p.bucket_batch(16) == 16
        assert p.bucket_batch(17) == 32
        # above the largest bucket: pass through unpadded (own compile)
        assert p.bucket_batch(100) == 100

    def test_spec_round_trip(self):
        p = BucketingPolicy.from_spec("batch=8,16;seq=pow2")
        assert p.batch_buckets == (8, 16)
        assert p.seq_buckets == "pow2"
        assert BucketingPolicy.from_spec(p.to_spec()) == p
        assert BucketingPolicy.from_spec("pow2").batch_buckets == "pow2"
        assert BucketingPolicy.from_spec("") is None
        assert BucketingPolicy.from_spec("none") is None

    def test_bad_specs_fail_fast(self):
        with pytest.raises(ValueError):
            BucketingPolicy.from_spec("batch=abc")
        with pytest.raises(ValueError):
            BucketingPolicy.from_spec("time=8")
        with pytest.raises(ValueError):
            BucketingPolicy(batch_buckets="fib")
        with pytest.raises(ValueError):
            BucketingPolicy(batch_buckets=(0, 8))

    def test_pad_batch_weights(self):
        p = BucketingPolicy(batch_buckets=(8,))
        x, y = _dense_data(n=5)
        xp, yp, mask, lmask, w = p.pad_batch(x, y)
        assert xp.shape == (8, 12) and yp.shape == (8, 5)
        np.testing.assert_array_equal(w, [1, 1, 1, 1, 1, 0, 0, 0])
        np.testing.assert_array_equal(xp[:5], x)
        assert (xp[5:] == 0).all() and (yp[5:] == 0).all()
        # full batch: no padding but the weights vector is still attached
        x8, y8 = _dense_data(n=8)
        xp, yp, _, _, w = p.pad_batch(x8, y8)
        assert xp.shape == (8, 12) and (w == 1).all()

    def test_conf_json_round_trip_mln(self):
        conf = (NeuralNetConfiguration.builder().batch_buckets((8, 16))
                .seq_buckets("pow2").list()
                .layer(DenseLayer(n_in=4, n_out=4))
                .layer(OutputLayer(n_in=4, n_out=2))
                .set_input_type(InputType.feed_forward(4)).build())
        back = MultiLayerConfiguration.from_json(conf.to_json())
        assert back.batch_buckets == (8, 16)
        assert back.seq_buckets == "pow2"

    def test_conf_json_round_trip_cg(self):
        g = (NeuralNetConfiguration.builder().batch_buckets("pow2")
             .graph_builder().add_inputs("in")
             .add_layer("out", OutputLayer(n_in=4, n_out=2), "in")
             .set_outputs("out").set_input_types((4,)).build())
        back = ComputationGraphConfiguration.from_json(g.to_json())
        assert back.batch_buckets == "pow2"
        assert back.seq_buckets is None

    def test_env_default(self, monkeypatch):
        from deeplearning4j_tpu.config import Environment

        monkeypatch.setenv("DL4J_TPU_BUCKETS", "batch=4,8")
        old = Environment._instance
        Environment._instance = None
        try:
            conf = (NeuralNetConfiguration.builder().list()
                    .layer(OutputLayer(n_in=4, n_out=2))
                    .set_input_type(InputType.feed_forward(4)).build())
            assert conf.batch_buckets == (4, 8)
        finally:
            Environment._instance = old

    def test_env_default_bad_spec_fails_fast(self, monkeypatch):
        from deeplearning4j_tpu.config import Environment

        monkeypatch.setenv("DL4J_TPU_BUCKETS", "batch=nope")
        old = Environment._instance
        Environment._instance = None
        try:
            with pytest.raises(ValueError, match="DL4J_TPU_BUCKETS"):
                NeuralNetConfiguration.builder()
        finally:
            Environment._instance = old


# ---------------------------------------------------------------------------
# Recompile-count regression: exactly N traces for a fixed bucket set
# ---------------------------------------------------------------------------
class TestRecompileCounts:
    def test_mln_ragged_epoch_traces(self):
        x, y = _dense_data(n=21)  # 21 % 8 = 5: ragged tail
        w = get_watcher()
        net = _mlp(buckets=None)
        with w.scope() as s:
            net.fit(ArrayDataSetIterator(x, y, batch=8), epochs=2)
            unbucketed = s.traces_of("MultiLayerNetwork.train_step")
        net = _mlp(buckets=(8,))
        with w.scope() as s:
            net.fit(ArrayDataSetIterator(x, y, batch=8), epochs=2)
            bucketed = s.traces_of("MultiLayerNetwork.train_step")
        assert unbucketed == 2  # full batch + ragged tail
        assert bucketed == 1    # ragged tail lands on the full-batch bucket

    def test_cg_ragged_epoch_traces(self):
        x, y = _dense_data(n=19, f=10, c=4)
        w = get_watcher()
        g = _cg(buckets=None)
        with w.scope() as s:
            g.fit(ArrayDataSetIterator(x, y, batch=8), epochs=2)
            unbucketed = s.traces_of("ComputationGraph.train_step")
        g = _cg(buckets=(8,))
        with w.scope() as s:
            g.fit(ArrayDataSetIterator(x, y, batch=8), epochs=2)
            bucketed = s.traces_of("ComputationGraph.train_step")
        assert unbucketed == 2
        assert bucketed == 1

    def test_bucket_set_bounds_traces_across_many_sizes(self):
        """Explicit bucket set {4, 8}: batches of size 1..8 in one run must
        compile at most twice (per-shape attribution in the watcher)."""
        w = get_watcher()
        net = _mlp(buckets=(4, 8))
        rng = np.random.default_rng(5)
        before = dict(w.shapes.get("MultiLayerNetwork.train_step", {}))
        with w.scope() as s:
            for n in (3, 1, 4, 7, 8, 2, 5, 6):
                x = rng.normal(size=(n, 12)).astype(np.float32)
                y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, n)]
                net._fit_batch(x, y)
            assert s.traces_of("MultiLayerNetwork.train_step") == 2
        new = [sig for sig, n in
               w.shapes["MultiLayerNetwork.train_step"].items()
               if n > before.get(sig, 0)]
        assert sorted(sig[0][0][0] for sig in new) == [4, 8]

    def test_tbptt_remainder_traces(self):
        xt = R.normal(size=(8, 11, 6)).astype(np.float32)  # k=4: segs 4,4,3
        yt = np.eye(3, dtype=np.float32)[
            R.integers(0, 3, (8, 11))].astype(np.float32)
        w = get_watcher()
        net = _mlp(seed=11, tbptt=4, recurrent=True)
        with w.scope() as s:
            net.fit(DataSet(xt, yt))
            unbucketed = s.traces_of("MultiLayerNetwork.tbptt_step")
        net = _mlp(seed=11, tbptt=4, recurrent=True, buckets=(8,),
                   seq=(4,))
        with w.scope() as s:
            net.fit(DataSet(xt, yt))
            bucketed = s.traces_of("MultiLayerNetwork.tbptt_step")
        assert unbucketed == 2  # full segment + length-3 remainder
        assert bucketed == 1    # remainder pads onto the (B, k) signature


# ---------------------------------------------------------------------------
# Bit-identity: bucketed == unpadded trajectories and metrics
# ---------------------------------------------------------------------------
class TestBitIdentity:
    def test_mln_fit_trajectory_and_evaluate(self):
        x, y = _dense_data(n=21)
        it = lambda: ArrayDataSetIterator(x, y, batch=8)  # noqa: E731
        a = _mlp(buckets=None)
        b = _mlp(buckets=(8,))
        a.fit(it(), epochs=3)
        b.fit(it(), epochs=3)
        assert _leaves_equal(a.params, b.params)
        assert float(a.score_value) == float(b.score_value)
        ea, eb = a.evaluate(it()), b.evaluate(it())
        assert ea.accuracy() == eb.accuracy()
        assert ea.f1() == eb.f1()
        # score() on a ragged batch (pads + weights) matches exactly
        assert a.score(x=x[:5], y=y[:5]) == b.score(x=x[:5], y=y[:5])
        # output() on a ragged batch: rows are sliced back, bit-equal
        np.testing.assert_array_equal(np.asarray(a.output(x[:3])),
                                      np.asarray(b.output(x[:3])))

    def test_cg_fit_trajectory_and_evaluate(self):
        x, y = _dense_data(n=19, f=10, c=4)
        it = lambda: ArrayDataSetIterator(x, y, batch=8)  # noqa: E731
        a = _cg(buckets=None)
        b = _cg(buckets=(8,))
        a.fit(it(), epochs=3)
        b.fit(it(), epochs=3)
        assert _leaves_equal(a.params, b.params)
        assert a.evaluate(it()).accuracy() == b.evaluate(it()).accuracy()
        assert a.score(x=x[:4], y=y[:4]) == b.score(x=x[:4], y=y[:4])

    def test_lstm_batch_bucketing(self):
        x = R.normal(size=(13, 12, 6)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[
            R.integers(0, 3, (13, 12))].astype(np.float32)
        it = lambda: ArrayDataSetIterator(x, y, batch=8)  # noqa: E731
        a = _mlp(recurrent=True, buckets=None)
        b = _mlp(recurrent=True, buckets=(8,))
        a.fit(it(), epochs=2)
        b.fit(it(), epochs=2)
        assert _leaves_equal(a.params, b.params)

    def test_lstm_seq_bucketing(self):
        """Time-axis padding (T=9 -> bucket 12) with generated masks is
        bit-identical to the unpadded run."""
        x = R.normal(size=(8, 9, 6)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[
            R.integers(0, 3, (8, 9))].astype(np.float32)
        a = _mlp(seed=9, recurrent=True)
        b = _mlp(seed=9, recurrent=True, seq=(12,))
        a.fit(DataSet(x, y))
        b.fit(DataSet(x, y))
        assert _leaves_equal(a.params, b.params)

    def test_tbptt_remainder_bit_identity(self):
        xt = R.normal(size=(8, 11, 6)).astype(np.float32)
        yt = np.eye(3, dtype=np.float32)[
            R.integers(0, 3, (8, 11))].astype(np.float32)
        a = _mlp(seed=11, tbptt=4, recurrent=True)
        b = _mlp(seed=11, tbptt=4, recurrent=True, buckets=(8,), seq=(4,))
        a.fit(DataSet(xt, yt))
        b.fit(DataSet(xt, yt))
        assert _leaves_equal(a.params, b.params)
        assert float(a.score_value) == float(b.score_value)


# ---------------------------------------------------------------------------
# AOT warmup
# ---------------------------------------------------------------------------
class TestWarmup:
    def test_mln_warmup_zero_traces(self):
        x, y = _dense_data(n=21)
        w = get_watcher()
        net = _mlp(buckets=(8, 16))
        built = net.warmup()
        assert built == 4  # 2 buckets x (train step + forward)
        with w.scope() as s:
            net.fit(ArrayDataSetIterator(x, y, batch=8), epochs=2)
            net.output(x[:3])
            assert s.traces == 0

    def test_warmup_matches_jit_path_exactly(self):
        x, y = _dense_data(n=21)
        warmed = _mlp(buckets=(8, 16))
        warmed.warmup()
        plain = _mlp(buckets=(8, 16))
        warmed.fit(ArrayDataSetIterator(x, y, batch=8), epochs=2)
        plain.fit(ArrayDataSetIterator(x, y, batch=8), epochs=2)
        assert _leaves_equal(warmed.params, plain.params)

    def test_cg_warmup_zero_traces(self):
        x, y = _dense_data(n=19, f=10, c=4)
        w = get_watcher()
        g = _cg(buckets=(8, 16))
        built = g.warmup()
        assert built == 4
        with w.scope() as s:
            g.fit(ArrayDataSetIterator(x, y, batch=8), epochs=2)
            g.output(x[:5])
            assert s.traces == 0

    def test_warmup_explicit_shapes(self):
        net = _mlp(buckets=(8,))
        assert net.warmup(shapes=[(16, 12)], inference=False) == 1
        w = get_watcher()
        x, y = _dense_data(n=16)
        with w.scope() as s:
            net._fit_batch(x, y)
            assert s.traces_of("MultiLayerNetwork.train_step") == 0

    def test_warmup_export_store_round_trip(self, tmp_path):
        """The on-disk AOT lowering store: a fresh net's warmup LOADS the
        serialized module (0 traces) and its trajectory matches the plain
        jit path bit-for-bit."""
        d = str(tmp_path / "aot")
        x, y = _dense_data(n=21)
        first = _mlp(buckets=(8,))
        assert first.warmup(export_dir=d) == 2
        from deeplearning4j_tpu.util import AotStore

        assert AotStore(d).entries() == 2
        w = get_watcher()
        fresh = _mlp(buckets=(8,))
        with w.scope() as s:
            fresh.warmup(export_dir=d)   # deserialize: no re-trace
            fresh.fit(ArrayDataSetIterator(x, y, batch=8), epochs=2)
            assert s.traces == 0
        plain = _mlp(buckets=(8,))
        plain.fit(ArrayDataSetIterator(x, y, batch=8), epochs=2)
        assert _leaves_equal(fresh.params, plain.params)
        np.testing.assert_array_equal(np.asarray(fresh.output(x[:3])),
                                      np.asarray(plain.output(x[:3])))

    def test_export_store_key_invalidates_on_conf_change(self, tmp_path):
        """A different model conf must MISS the store (fresh export), never
        load a stale lowering."""
        d = str(tmp_path / "aot2")
        _mlp(buckets=(8,), seed=7).warmup(export_dir=d, inference=False)
        from deeplearning4j_tpu.util import AotStore

        assert AotStore(d).entries() == 1
        _mlp(buckets=(8,), seed=8).warmup(export_dir=d, inference=False)
        assert AotStore(d).entries() == 2  # different seed -> different key

    def test_warmup_requires_init_and_buckets(self):
        conf = (NeuralNetConfiguration.builder().list()
                .layer(OutputLayer(n_in=4, n_out=2))
                .set_input_type(InputType.feed_forward(4)).build())
        net = MultiLayerNetwork(conf)
        with pytest.raises(ValueError, match="init"):
            net.warmup()
        net.init()
        with pytest.raises(ValueError, match="batch_buckets"):
            net.warmup()  # no bucketing configured, no shapes given


# ---------------------------------------------------------------------------
# SameDiff cross-instance executable cache
# ---------------------------------------------------------------------------
class TestSameDiffExecCache:
    @staticmethod
    def _build_graph():
        from deeplearning4j_tpu.samediff import SameDiff

        sd = SameDiff()
        x = sd.placeholder("x", shape=(4, 3))
        w = sd.var("w", np.arange(12, dtype=np.float32).reshape(3, 4) / 10)
        h = sd.math.tanh(sd.linalg.mmul(x, w))
        out = sd.math.mul(h, h)
        return sd, out.name

    def test_fresh_reload_hits_exec_cache(self):
        watcher = get_watcher()
        feed = {"x": R.normal(size=(4, 3)).astype(np.float32)}
        sd1, out1 = self._build_graph()
        with watcher.scope() as s:
            r1 = sd1.output(feed, [out1])
            first = s.traces_of("SameDiff.output")
        assert first == 1
        sd2, out2 = self._build_graph()  # fresh in-process "reload"
        assert sd1.fingerprint() == sd2.fingerprint()
        with watcher.scope() as s:
            r2 = sd2.output(feed, [out2])
            assert s.traces_of("SameDiff.output") == 0  # exec-cache hit
        np.testing.assert_array_equal(r1[out1], r2[out2])

    def test_different_graph_misses(self):
        watcher = get_watcher()
        sd1, out1 = self._build_graph()
        feed = {"x": R.normal(size=(4, 3)).astype(np.float32)}
        sd1.output(feed, [out1])
        from deeplearning4j_tpu.samediff import SameDiff

        sd3 = SameDiff()
        x = sd3.placeholder("x", shape=(4, 3))
        w = sd3.var("w", np.arange(12, dtype=np.float32).reshape(3, 4) / 10)
        out3 = sd3.math.sin(sd3.linalg.mmul(x, w))  # different op
        assert sd3.fingerprint() != sd1.fingerprint()
        with watcher.scope() as s:
            sd3.output(feed, [out3.name])
            assert s.traces_of("SameDiff.output") == 1

    def test_mutation_invalidates_fingerprint(self):
        sd, out = self._build_graph()
        fp = sd.fingerprint()
        sd.math.add(sd.get_variable(out), sd.get_variable(out))
        assert sd.fingerprint() != fp


# ---------------------------------------------------------------------------
# Persistent on-disk compilation cache
# ---------------------------------------------------------------------------
class TestPersistentCache:
    """One rule (util/compile_cache.py): JAX_COMPILATION_CACHE_DIR set ->
    JAX has it and the program sets no directory in code; unset -> one
    fixed, git-ignored path inside the checkout."""

    def test_unset_goes_to_the_fixed_path_in_the_checkout(
            self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.util import (cache_entries, compile_cache,
                                             disable_persistent_cache,
                                             enable_persistent_cache)

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache.FIXED_CACHE_DIR == os.path.join(
            repo, ".jax_cache")
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        # the rule is under test, not the checkout: keep this run's
        # executables out of the real cache directory
        d = str(tmp_path / "fixed")
        monkeypatch.setattr(compile_cache, "FIXED_CACHE_DIR", d)
        try:
            assert enable_persistent_cache() == d and os.path.isdir(d)
            assert jax.config.jax_compilation_cache_dir == d

            @jax.jit
            def f(a):
                return a * 3 + 1

            f(np.ones(7, np.float32))
            assert cache_entries() >= 1
        finally:
            disable_persistent_cache()
        assert jax.config.jax_compilation_cache_dir is None

    def test_set_variable_is_left_to_jax(self, tmp_path):
        """A fresh process with the variable set: after enable, the
        directory is the one JAX read from the environment, and the fixed
        path was not created or pointed at."""
        child = (
            "import os, jax\n"
            "from deeplearning4j_tpu.util import compile_cache as cc\n"
            "got = cc.enable_persistent_cache()\n"
            "assert got == os.environ['JAX_COMPILATION_CACHE_DIR'], got\n"
            "assert jax.config.jax_compilation_cache_dir == got\n"
            "assert got != cc.FIXED_CACHE_DIR\n"
            "print('left-to-jax', got)\n"
        )
        d = str(tmp_path / "from-outside")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=d)
        out = subprocess.run([sys.executable, "-c", child], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        assert f"left-to-jax {d}" in out.stdout

    def test_second_process_hits_cache(self, tmp_path):
        """Cross-process: a restarted process deserializes instead of
        recompiling (what `setup_s` and `setup_cache_hit_share` read on the
        chip: PERF.md section 3, compile). The parent places the throwaway
        cache from outside."""
        child = (
            "import sys, json, jax\n"
            "from deeplearning4j_tpu.util import (enable_persistent_cache,"
            " get_watcher)\n"
            "enable_persistent_cache()\n"
            "import numpy as np\n"
            "w = get_watcher()\n"
            "f = jax.jit(lambda a: (a @ a.T).sum() * 2)\n"
            "f(np.ones((32, 32), np.float32))\n"
            "print(json.dumps(w.counts()))\n"
        )
        d = str(tmp_path / "cc2")

        def run():
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       JAX_COMPILATION_CACHE_DIR=d)
            out = subprocess.run([sys.executable, "-c", child], env=env,
                                 capture_output=True, text=True, timeout=300)
            assert out.returncode == 0, out.stderr[-2000:]
            return json.loads(out.stdout.strip().splitlines()[-1])

        cold, warm = run(), run()
        assert cold["persistent_cache_hits"] == 0
        assert warm["persistent_cache_hits"] > 0
        # jax logs a backend_compile event even on a cache hit; the honest
        # recompile count is compiles minus hits
        assert warm["uncached_compiles"] < cold["uncached_compiles"]


# ---------------------------------------------------------------------------
# Observability: watcher, listener, stats
# ---------------------------------------------------------------------------
class TestObservabilitySurface:
    def test_watcher_counts_and_summary(self):
        w = get_watcher()
        with w.scope() as s:
            f = jax.jit(lambda a: a + 1)
            f(np.ones(3, np.float32))
            assert s.backend_compiles >= 1
        counts = w.counts()
        assert {"traces", "backend_compiles", "persistent_cache_hits",
                "total_traces"} <= set(counts)
        assert "CompileWatcher" in w.summary()

    def test_recompile_listener_flags_new_shapes(self):
        from deeplearning4j_tpu.nn.listeners import RecompileListener

        logs = []
        net = _mlp()
        lst = RecompileListener(grace=1, log_fn=logs.append)
        net.set_listeners(lst)
        x, y = _dense_data(n=8)
        net.fit(x, y)   # iteration 1: inside grace, no event
        assert not lst.events
        x2, y2 = _dense_data(n=6)
        net.fit(x2, y2)  # new shape past grace: recompile event
        assert lst.events and lst.events[0][1] == "MultiLayerNetwork.train_step"
        assert logs and "RECOMPILE" in logs[0]

    def test_stats_listener_records_compile_group(self):
        from deeplearning4j_tpu.util import InMemoryStatsStorage, StatsListener

        store = InMemoryStatsStorage()
        net = _mlp()
        net.set_listeners(StatsListener(store, frequency=1,
                                        collect_histograms=False))
        x, y = _dense_data(n=8)
        net.fit(x, y)
        rec = store.records[-1]
        assert "compile" in rec
        assert rec["compile"]["total_traces"] >= 1


# ---------------------------------------------------------------------------
# Bucketed serving (ParallelInference)
# ---------------------------------------------------------------------------
class TestBucketedServing:
    def test_inference_bucketing_bounds_signatures(self):
        from deeplearning4j_tpu.parallel import ParallelInference, TrainingMesh

        net = _mlp(buckets=(8, 16))
        pi = ParallelInference(net, mesh=TrainingMesh(
            data=1, devices=jax.devices()[:1]))
        assert pi.bucketing is not None  # inherited from the model conf
        w = get_watcher()
        x, _ = _dense_data(n=16)
        with w.scope() as s:
            for n in (3, 5, 7, 8, 2, 6):
                out = pi.output(x[:n])
                assert out.shape == (n, 5)
            assert s.traces_of("MultiLayerNetwork.forward") <= 1

    def test_inference_warmup(self):
        from deeplearning4j_tpu.parallel import ParallelInference, TrainingMesh

        net = _mlp(buckets=(8, 16))
        pi = ParallelInference(net, mesh=TrainingMesh(
            data=1, devices=jax.devices()[:1]))
        assert pi.warmup() == 2
        w = get_watcher()
        x, _ = _dense_data(n=16)
        with w.scope() as s:
            pi.output(x[:5])
            pi.output(x[:13])
            assert s.traces_of("MultiLayerNetwork.forward") == 0

    def test_wrapper_warmup_preserves_model_state(self):
        from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMesh

        net = _mlp(buckets=(8,))
        before = jax.tree_util.tree_map(np.asarray, net.params)
        pw = ParallelWrapper(net, mesh=TrainingMesh(
            data=2, devices=jax.devices()[:2]))
        assert pw.warmup([8]) == 1
        assert _leaves_equal(before, net.params)
