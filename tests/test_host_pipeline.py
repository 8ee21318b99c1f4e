"""Async host-pipeline tests (ISSUE 2): multiprocess TransformProcess
executor, device-prefetch iterator, and sync-free (coalesced) listener
orchestration.

Invariants under test, per the acceptance criteria:
- multiprocess executor output is BIT-IDENTICAL to single-process on a CSV
  corpus (including order under record-dropping filters);
- prefetch staging of batch k+1 never mutates batch k's buffers (donation
  safety — the train step donates params/opt state, never batch arrays, and
  device_put allocates fresh buffers);
- a worker exception propagates to fit() (timeout + re-raise) instead of
  hanging the queue;
- sync_every > 1 training is loss-trajectory-equivalent to sync_every = 1
  (same final params, fixed seed), and listeners still receive EVERY
  iteration's scalars — just coalesced, already materialized.
"""

import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.data import (
    ArrayDataSetIterator,
    AsyncDataSetIterator,
    DataSet,
    PrefetchStalledError,
)
from deeplearning4j_tpu.datavec import (
    CSVRecordReader,
    MultiProcessTransformExecutor,
    ParallelTransformRecordReader,
    RecordReaderDataSetIterator,
    Schema,
    TransformExecutionError,
    TransformProcess,
    TransformProcessRecordReader,
)
from deeplearning4j_tpu.nn import (
    InputType,
    MultiLayerNetwork,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.layers import (
    ConvolutionLayer,
    DenseLayer,
    OutputLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.updaters import Adam

# The deadline of every executor and prefetch worker a test starts here and
# expects to finish: their own defaults (300 s, 120 s) would let one hang
# take a fifth of the suite's limit.
HANG_S = 30.0


# --------------------------------------------------------------------------
# multiprocess TransformProcess executor
# --------------------------------------------------------------------------

@pytest.fixture
def iris_csv(tmp_path):
    p = tmp_path / "iris.csv"
    rng = np.random.default_rng(0)
    lines = []
    for i in range(120):
        f = rng.uniform(0, 8, 4)
        lines.append(",".join(f"{v:.2f}" for v in f) + f",{i % 3}")
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def _iris_schema():
    return (
        Schema.builder()
        .add_column_double("sl").add_column_double("sw")
        .add_column_double("pl").add_column_double("pw")
        .add_column_integer("label")
        .build()
    )


def _iris_tp():
    """Arithmetic + a record-dropping filter: order preservation under
    drops is exactly what the contiguous-chunk merge must get right."""
    return (
        TransformProcess.builder(_iris_schema())
        .double_column_transform("sl", lambda v: v * 2.0 + 0.25)
        .filter(lambda r, schema: float(r[1]) > 6.0)  # drop ~25% of records
        .double_column_transform("pw", lambda v: v - 1.0)
        .build()
    )


def test_mp_executor_bit_identical_to_serial(iris_csv):
    records = list(CSVRecordReader(iris_csv))
    tp = _iris_tp()
    serial = tp.execute(records)
    for workers in (2, 4):
        ex = MultiProcessTransformExecutor(
            tp, num_workers=workers, min_records_per_worker=1,
            timeout=HANG_S)
        assert ex.execute(records) == serial  # exact, order included


def test_mp_executor_worker_count_from_the_environment(iris_csv,
                                                        monkeypatch):
    """``num_workers=None`` takes DL4J_TPU_ETL_WORKERS: with two forced,
    the executor forks two workers (not the serial fallback, not one per
    core) and their merge is still the serial result."""
    from deeplearning4j_tpu import config as cfg

    monkeypatch.setenv("DL4J_TPU_ETL_WORKERS", "2")
    monkeypatch.setattr(cfg.Environment, "_instance", None)
    records = list(CSVRecordReader(iris_csv))
    tp = _iris_tp()
    ex = MultiProcessTransformExecutor(tp, min_records_per_worker=1,
                                       timeout=HANG_S)
    assert ex.num_workers == 2
    assert ex.execute(records) == tp.execute(records)


def test_mp_executor_small_input_serial_path(iris_csv):
    # below 2*min_records_per_worker the serial path runs — still identical
    records = list(CSVRecordReader(iris_csv))[:10]
    tp = _iris_tp()
    ex = MultiProcessTransformExecutor(tp, num_workers=4, timeout=HANG_S,
                                       min_records_per_worker=64)
    assert ex.execute(records) == tp.execute(records)


def test_mp_executor_worker_exception_propagates(iris_csv):
    records = list(CSVRecordReader(iris_csv))

    def boom(v):
        if v > 7.0:
            raise ValueError("bad record in worker")
        return v

    tp = (TransformProcess.builder(_iris_schema())
          .double_column_transform("sl", boom).build())
    ex = MultiProcessTransformExecutor(tp, num_workers=2, timeout=HANG_S,
                                       min_records_per_worker=1)
    with pytest.raises(TransformExecutionError, match="bad record in worker"):
        ex.execute(records)


def test_mp_executor_timeout_no_hang(iris_csv):
    records = list(CSVRecordReader(iris_csv))

    def wedge(v):
        time.sleep(60.0)
        return v

    tp = (TransformProcess.builder(_iris_schema())
          .double_column_transform("sl", wedge).build())
    ex = MultiProcessTransformExecutor(tp, num_workers=2, timeout=0.3,
                                       min_records_per_worker=1)
    t0 = time.perf_counter()
    with pytest.raises(TransformExecutionError, match="timed out"):
        ex.execute(records)
    assert time.perf_counter() - t0 < 30.0  # raised, not wedged


def test_parallel_record_reader_bridges_to_iterator(iris_csv):
    """ParallelTransformRecordReader drop-in where TransformProcessRecordReader
    goes: the DataSetIterator batches must match bit-for-bit."""
    tp = _iris_tp()
    base = TransformProcessRecordReader(CSVRecordReader(iris_csv), tp)
    par = ParallelTransformRecordReader(CSVRecordReader(iris_csv), tp,
                                        num_workers=2, timeout=HANG_S)
    it_serial = RecordReaderDataSetIterator(base, 16, label_index=4,
                                            num_classes=3)
    it_par = RecordReaderDataSetIterator(par, 16, label_index=4,
                                         num_classes=3)
    ds_s = list(it_serial)
    ds_p = list(it_par)
    assert len(ds_s) == len(ds_p) > 0
    for a, b in zip(ds_s, ds_p):
        np.testing.assert_array_equal(np.asarray(a.features),
                                      np.asarray(b.features))
        np.testing.assert_array_equal(np.asarray(a.labels),
                                      np.asarray(b.labels))


# --------------------------------------------------------------------------
# device-prefetch iterator
# --------------------------------------------------------------------------

def _batches(n=6, batch=4, feat=3, seed=0):
    rng = np.random.default_rng(seed)
    return [DataSet(rng.normal(size=(batch, feat)).astype(np.float32),
                    np.eye(2, dtype=np.float32)[rng.integers(0, 2, batch)])
            for _ in range(n)]


class _ListIterator:
    def __init__(self, batches):
        self.batches = batches

    def __iter__(self):
        return iter(self.batches)

    def reset(self):
        pass

    def batch_size(self):
        return len(self.batches[0].features)


def test_prefetch_batch_size_over_attribute_style_base(iris_csv):
    """RecordReaderDataSetIterator stores batch_size as an int ATTRIBUTE
    (shadowing the DataSetIterator method); the wrapper must handle both."""
    base = RecordReaderDataSetIterator(
        TransformProcessRecordReader(CSVRecordReader(iris_csv), _iris_tp()),
        16, label_index=4, num_classes=3)
    assert AsyncDataSetIterator(base).batch_size() == 16
    assert AsyncDataSetIterator(_ListIterator(_batches(2))).batch_size() == 4


def test_prefetch_yields_all_batches_in_order():
    src = _batches(8)
    out = list(AsyncDataSetIterator(_ListIterator(src), buffer_size=2,
                                    timeout=HANG_S))
    assert len(out) == 8
    for a, b in zip(src, out):
        np.testing.assert_array_equal(np.asarray(a.features),
                                      np.asarray(b.features))


def test_prefetch_stages_on_device():
    it = AsyncDataSetIterator(_ListIterator(_batches(3)), buffer_size=2,
                              timeout=HANG_S)
    for ds in it:
        # staged arrays are device-resident jax Arrays, not host numpy
        assert hasattr(ds.features, "devices")
        assert hasattr(ds.labels, "devices")


def test_prefetch_donation_safety():
    """Batch k's buffers must not be touched by the in-flight device_put of
    batch k+1: hold every received batch, snapshot on receipt, let the
    worker run ahead, then verify all snapshots still match."""
    src = _batches(8)
    it = AsyncDataSetIterator(_ListIterator(src), buffer_size=2,
                              timeout=HANG_S)
    held, snaps = [], []
    for ds in it:
        held.append(ds)
        snaps.append((np.asarray(ds.features).copy(),
                      np.asarray(ds.labels).copy()))
        time.sleep(0.01)  # worker stages k+1 (and k+2) while k is "computing"
    assert len(held) == 8
    seen = set()
    for src_ds, ds, (fx, fy) in zip(src, held, snaps):
        # fresh buffers, not aliases of each other...
        assert id(ds.features) not in seen
        seen.add(id(ds.features))
        # ...and still exactly the source batch after the pipeline drained
        np.testing.assert_array_equal(np.asarray(ds.features), fx)
        np.testing.assert_array_equal(np.asarray(ds.labels), fy)
        np.testing.assert_array_equal(np.asarray(src_ds.features), fx)


class _BoomIterator(_ListIterator):
    def __init__(self, batches, fail_after):
        super().__init__(batches)
        self.fail_after = fail_after

    def __iter__(self):
        for i, ds in enumerate(self.batches):
            if i == self.fail_after:
                raise RuntimeError("ETL worker exploded")
            yield ds


def test_prefetch_worker_exception_reraises():
    it = AsyncDataSetIterator(_BoomIterator(_batches(6), fail_after=2),
                              buffer_size=2, timeout=HANG_S)
    got = []
    with pytest.raises(RuntimeError, match="ETL worker exploded"):
        for ds in it:
            got.append(ds)
    assert len(got) == 2  # the good batches arrived first


def test_prefetch_worker_exception_propagates_to_fit():
    net = _lenet(seed=3, sync_every=2)
    x = np.random.default_rng(0).normal(size=(4, 28, 28, 1)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[[1, 2, 3, 4]]
    batches = [DataSet(x, y) for _ in range(5)]
    it = AsyncDataSetIterator(_BoomIterator(batches, fail_after=3),
                              buffer_size=2, timeout=HANG_S)
    with pytest.raises(RuntimeError, match="ETL worker exploded"):
        net.fit(it, epochs=1)


class _WedgedIterator(_ListIterator):
    def __iter__(self):
        yield self.batches[0]
        threading.Event().wait(60.0)  # daemon worker; abandoned on timeout


def test_prefetch_stalled_worker_times_out():
    it = AsyncDataSetIterator(_WedgedIterator(_batches(2)), buffer_size=2,
                              timeout=0.5)
    t0 = time.perf_counter()
    with pytest.raises(PrefetchStalledError, match="no batch for 0.5s"):
        list(it)
    assert time.perf_counter() - t0 < 30.0


# --------------------------------------------------------------------------
# sync-free (coalesced) step orchestration
# --------------------------------------------------------------------------

def _lenet(seed=0, sync_every=1):
    conf = (
        NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
        .sync_every(sync_every).list()
        .layer(ConvolutionLayer(n_out=8, kernel_size=(5, 5),
                                padding="VALID", activation="relu"))
        .layer(SubsamplingLayer(kernel_size=(2, 2)))
        .layer(ConvolutionLayer(n_out=16, kernel_size=(5, 5),
                                padding="VALID", activation="relu"))
        .layer(SubsamplingLayer(kernel_size=(2, 2)))
        .layer(DenseLayer(n_out=32, activation="relu"))
        .layer(OutputLayer(n_out=10))
        .set_input_type(InputType.convolutional(28, 28, 1))
        .build()
    )
    return MultiLayerNetwork(conf).init()


class _RecordingListener:
    def __init__(self):
        self.calls = []  # (iteration, epoch, score)

    def iteration_done(self, model, iteration, epoch):
        self.calls.append((iteration, epoch, model.score_value))


def _mnist_like(n=32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 28, 28, 1)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, n)]
    return x, y


def test_sync_every_param_trajectory_equivalent():
    """sync_every only changes WHEN the host observes the loss, never the
    math: fixed-seed LeNet runs must land on bit-identical final params."""
    import jax

    x, y = _mnist_like(32)
    data = lambda: ArrayDataSetIterator(x, y, batch=8)  # noqa: E731
    net1 = _lenet(seed=7, sync_every=1)
    net1.set_listeners(_RecordingListener())
    net1.fit(data(), epochs=2)
    net4 = _lenet(seed=7, sync_every=4)
    net4.set_listeners(_RecordingListener())
    net4.fit(data(), epochs=2)
    for p1, p4 in zip(net1.params, net4.params):
        for l1, l4 in zip(jax.tree_util.tree_leaves(p1),
                          jax.tree_util.tree_leaves(p4)):
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l4))


def test_sync_every_listeners_see_every_iteration_coalesced():
    x, y = _mnist_like(24)
    rec1, rec3 = _RecordingListener(), _RecordingListener()

    net1 = _lenet(seed=11, sync_every=1)
    net1.set_listeners(rec1)
    net1.fit(ArrayDataSetIterator(x, y, batch=8), epochs=2)

    net3 = _lenet(seed=11, sync_every=3)
    net3.set_listeners(rec3)
    counts = []
    for ds in ArrayDataSetIterator(x, y, batch=8):
        net3._fit_batch(np.asarray(ds.features), np.asarray(ds.labels))
        counts.append(len(rec3.calls))
    # 3 batches/epoch with window 3: nothing observed until the window fills
    assert counts == [0, 0, 3]
    net3._end_epoch()
    for ds in ArrayDataSetIterator(x, y, batch=8):
        net3._fit_batch(np.asarray(ds.features), np.asarray(ds.labels))
    net3._end_epoch()

    # every iteration's scalar arrived, in order, already materialized...
    assert [(c[0], c[1]) for c in rec3.calls] == \
        [(c[0], c[1]) for c in rec1.calls]
    assert all(isinstance(c[2], float) for c in rec3.calls)
    # ...and with the same values the per-step sync cadence observed
    np.testing.assert_allclose([c[2] for c in rec3.calls],
                               [c[2] for c in rec1.calls], rtol=1e-6)


def test_sync_every_flushes_at_epoch_end():
    """A window mid-fill at epoch end must flush so on_epoch_end callbacks
    observe a complete epoch (sync_every larger than batches/epoch)."""
    x, y = _mnist_like(16, seed=2)
    rec = _RecordingListener()
    net = _lenet(seed=5, sync_every=100)
    net.set_listeners(rec)
    net.fit(ArrayDataSetIterator(x, y, batch=8), epochs=1)
    assert [(c[0], c[1]) for c in rec.calls] == [(1, 0), (2, 0)]


def test_sync_every_validation_and_json_round_trip():
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration

    with pytest.raises(ValueError, match="sync_every"):
        NeuralNetConfiguration.builder().sync_every(0)
    conf = (NeuralNetConfiguration.builder().seed(1).sync_every(6).list()
            .layer(DenseLayer(n_in=4, n_out=2)).layer(OutputLayer(n_out=2))
            .build())
    assert conf.sync_every == 6
    rt = MultiLayerConfiguration.from_json(conf.to_json())
    assert rt.sync_every == 6
    # legacy JSON without the field defaults to the per-step cadence
    import json as _json
    d = _json.loads(conf.to_json())
    del d["sync_every"]
    assert MultiLayerConfiguration.from_json(_json.dumps(d)).sync_every == 1


def _graph_conf(sync_every):
    return (
        NeuralNetConfiguration.builder().seed(2).updater(Adam(0.01))
        .sync_every(sync_every)
        .graph_builder()
        .add_inputs("in")
        .add_layer("d", DenseLayer(n_in=4, n_out=8, activation="tanh"), "in")
        .add_layer("out", OutputLayer(n_in=8, n_out=3), "d")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(4))
        .build()
    )


def test_sync_every_graph_json_round_trip():
    from deeplearning4j_tpu.nn.computation_graph import (
        ComputationGraphConfiguration,
    )

    conf = _graph_conf(5)
    assert conf.sync_every == 5
    rt = ComputationGraphConfiguration.from_json(conf.to_json())
    assert rt.sync_every == 5


def test_sync_every_graph_fit_equivalent_and_coalesced():
    """Same invariants on the ComputationGraph fit path: bit-equal params
    and the full per-iteration scalar stream under coalesced dispatch."""
    import jax

    from deeplearning4j_tpu.nn.computation_graph import ComputationGraph

    rng = np.random.default_rng(0)
    x = rng.normal(size=(24, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 24)]
    nets, recs = [], []
    for se in (1, 3):
        net = ComputationGraph(_graph_conf(se)).init()
        rec = _RecordingListener()
        net.listeners.append(rec)
        net.fit(ArrayDataSetIterator(x, y, batch=4), epochs=2)
        nets.append(net)
        recs.append(rec)
    assert [c[:2] for c in recs[1].calls] == [c[:2] for c in recs[0].calls]
    np.testing.assert_allclose([c[2] for c in recs[1].calls],
                               [c[2] for c in recs[0].calls], rtol=1e-6)
    for pa, pb in zip(nets[0].params.values(), nets[1].params.values()):
        for la, lb in zip(jax.tree_util.tree_leaves(pa),
                          jax.tree_util.tree_leaves(pb)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_sync_every_env_default(monkeypatch):
    from deeplearning4j_tpu.config import Environment

    monkeypatch.setenv("DL4J_TPU_SYNC_EVERY", "8")
    env = Environment()
    assert env.default_sync_every == 8
    monkeypatch.setenv("DL4J_TPU_SYNC_EVERY", "0")
    with pytest.raises(ValueError, match="DL4J_TPU_SYNC_EVERY"):
        Environment()
