"""Native runtime: arena, CSV parse, async pipeline.

Reference test parity: libnd4j gtest suites cover the native core
(SURVEY.md §4 row 1); here the native module is the ETL/memory runtime and
is validated against the pure-Python implementations. The pipeline's
concurrency is additionally stress-tested under TSan/ASan out-of-band (see
csrc comments)."""

import os

import numpy as np
import pytest

from deeplearning4j_tpu import native

pytestmark = pytest.mark.skipif(
    not native.is_available(),
    reason=f"native build unavailable: {native.build_error()}")


class TestArena:
    def test_alloc_views_and_reset(self):
        with native.HostArena(1 << 16) as ar:
            a = ar.alloc_array((8, 8))
            a[:] = 3.0
            b = ar.alloc_array((4,), np.int32)
            b[:] = 7
            assert float(a.sum()) == 192.0
            assert ar.used() >= a.nbytes + b.nbytes
            ar.reset()
            assert ar.used() == 0
            c = ar.alloc_array((8, 8))
            c[:] = 1.0  # reuses the same slab

    def test_alignment_and_exhaustion(self):
        with native.HostArena(4096) as ar:
            v = ar.alloc_array((4,), np.float32, align=256)
            assert v.ctypes.data % 256 == 0
            with pytest.raises(MemoryError):
                ar.alloc_array((100000,), np.float32)


class TestCSVParse:
    def test_matches_python_parse(self, rng):
        rows = rng.normal(size=(200, 7)).astype(np.float32)
        text = "\n".join(",".join(f"{v:.6f}" for v in r) for r in rows)
        out = native.parse_csv(text.encode(), 7)
        np.testing.assert_allclose(out, rows, atol=1e-5)

    def test_handles_blank_lines_and_crlf(self):
        out = native.parse_csv(b"1,2\r\n\r\n3,4\r\n", 2)
        np.testing.assert_array_equal(out, [[1, 2], [3, 4]])

    def test_non_numeric_becomes_nan(self):
        out = native.parse_csv(b"1,abc\n2,3\n", 2)
        assert np.isnan(out[0, 1]) and out[1, 1] == 3

    def test_malformed_raises(self):
        with pytest.raises(ValueError):
            native.parse_csv(b"1,2\n3,4,5\n", 2)


class TestAsyncPipeline:
    def _files(self, tmp_path, n=8, rows=50, cols=3):
        paths = []
        for i in range(n):
            p = tmp_path / f"part{i}.csv"
            p.write_text("\n".join(
                ",".join(f"{i}.0" if c == 0 else f"{r}.5" for c in range(cols))
                for r in range(rows)))
            paths.append(str(p))
        return paths

    def test_delivers_all_files_in_order(self, tmp_path):
        paths = self._files(tmp_path)
        pipe = native.AsyncCSVPipeline(paths, cols=3, n_threads=3, prefetch=2)
        seen = []
        for idx, arr in pipe:
            assert arr.shape == (50, 3)
            assert arr[0, 0] == float(idx)  # right file's data
            seen.append(idx)
        pipe.close()
        assert seen == list(range(8))

    def test_matches_single_threaded_reference(self, tmp_path, rng):
        paths = []
        ref = []
        for i in range(4):
            data = rng.normal(size=(20, 4)).astype(np.float32)
            p = tmp_path / f"r{i}.csv"
            p.write_text("\n".join(",".join(f"{v:.6f}" for v in r) for r in data))
            paths.append(str(p))
            ref.append(data)
        pipe = native.AsyncCSVPipeline(paths, cols=4, n_threads=4, prefetch=1)
        for idx, arr in pipe:
            np.testing.assert_allclose(arr, ref[idx], atol=1e-5)
        pipe.close()

    def test_many_workers_one_slot_strands_no_waiter(self, tmp_path):
        """Two lost wakeups that `next()` never returned from. Four workers
        wait on one free slot and the one that is woken may find its file
        already emitted and leave: waking one waiter per slot (as `pipe_next`
        did) strands the rest, and the first pipeline of this shape hung,
        every time. And the last worker counted itself done outside the
        mutex, between the consumer's look at that count and its sleep: one
        pipeline in some hundreds on a busy machine."""
        import threading

        paths = self._files(tmp_path, n=6, rows=5)
        rounds = []

        def drain():
            for _ in range(200):
                pipe = native.AsyncCSVPipeline(paths, cols=3, n_threads=4,
                                               prefetch=1)
                rounds.append([idx for idx, _arr in pipe])
                pipe.close()

        t = threading.Thread(target=drain, daemon=True)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive(), f"hung in pipeline {len(rounds) + 1} of 200"
        assert rounds == [list(range(6))] * 200

    def test_unreadable_file_raises(self, tmp_path):
        paths = self._files(tmp_path, n=2)
        paths.insert(1, str(tmp_path / "missing.csv"))
        pipe = native.AsyncCSVPipeline(paths, cols=3)
        next(pipe)
        with pytest.raises(IOError):
            while True:
                next(pipe)
        pipe.close()

    def test_early_close_no_hang(self, tmp_path):
        paths = self._files(tmp_path, n=8)
        pipe = native.AsyncCSVPipeline(paths, cols=3, n_threads=2, prefetch=1)
        next(pipe)
        pipe.close()  # workers blocked on a full ring must exit


class TestNativeDataSetIterator:
    def test_trains_a_network(self, tmp_path, rng):
        from deeplearning4j_tpu.native.dataset import NativeCSVDataSetIterator
        from deeplearning4j_tpu.nn import (
            InputType, MultiLayerNetwork, NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Adam

        centers = rng.standard_normal((3, 4)) * 3
        paths = []
        for i in range(4):
            ys = rng.integers(0, 3, 64)
            xs = centers[ys] + rng.standard_normal((64, 4))
            rows = np.concatenate([xs, ys[:, None]], 1)
            p = tmp_path / f"shard{i}.csv"
            p.write_text("\n".join(",".join(f"{v:.5f}" for v in r) for r in rows))
            paths.append(str(p))
        it = NativeCSVDataSetIterator(paths, batch_size=32, n_columns=5,
                                      label_index=-1, num_classes=3)
        batches = list(it)
        assert sum(len(b.features) for b in batches) == 256
        assert batches[0].features.shape == (32, 4)
        assert batches[0].labels.shape == (32, 3)

        conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(0.01))
                .list()
                .layer(DenseLayer(n_in=4, n_out=16, activation="relu"))
                .layer(OutputLayer(n_in=16, n_out=3, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.feed_forward(4)).build())
        net = MultiLayerNetwork(conf).init()
        net.fit(it, epochs=8)
        ev = net.evaluate(it)
        assert ev.accuracy() > 0.8, ev.accuracy()
