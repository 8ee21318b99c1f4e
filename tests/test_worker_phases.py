"""The serving worker's seven phases (PR 39, docs/OBSERVABILITY.md
#worker-phases) on a tiny paged decoder behind ``BatchScheduler``: the
phase counters tile the worker thread's time, the turnaround between
batches leaves out the wait for an arrival and is the phases between two
batches when there is a queue, the phases reach a ``jax.profiler`` trace
on a host plane in order, and with telemetry off nothing is recorded and
no clock is read."""

import glob
import time
import types

import numpy as np
import pytest

from deeplearning4j_tpu.data.bucketing import BucketingPolicy
from deeplearning4j_tpu.serving import ServingModel
from deeplearning4j_tpu.serving import scheduler as sch
from deeplearning4j_tpu.serving.scheduler import BatchScheduler
from deeplearning4j_tpu.util import telemetry as tm

PHASES = (sch.WAIT_WORK, sch.FILL, sch.PREP, sch.LAUNCH, sch.WAIT, sch.DRAIN,
          sch.RESPOND)
PROMPT = np.asarray([1, 2, 3], np.int32)


@pytest.fixture(autouse=True)
def tele():
    t = tm.get_telemetry()
    was = t.enabled
    t.reset()
    t.enabled = True
    yield t
    t.enabled = was
    t.reset()


@pytest.fixture(scope="module")
def model():
    from deeplearning4j_tpu.zoo.bert import Bert

    bert = Bert.tiny(causal=True, task="mlm", vocab_size=29, max_length=16,
                     hidden_dropout=0.0).init()
    m = ServingModel(bert, "ph", kind="generate",
                     bucketing=BucketingPolicy(batch_buckets=(1, 2),
                                               seq_buckets=(8,)))
    m.warmup()
    return m


def _counter(t, name):
    return t.counter_total(name, model="ph")


def _phase_s(t, name):
    return _counter(t, tm._phase_counter(name))


def _ask(sched, n=1):
    futs = [sched.submit(PROMPT, lane="batch", max_new_tokens=4)
            for _ in range(n)]
    return [f.result(timeout=60) for f in futs]


def test_the_phase_counters_tile_the_worker_threads_time(model, tele):
    t0 = time.perf_counter()
    sched = BatchScheduler(model, max_wait_ms=1.0).start()
    for n in (1, 3, 2):
        _ask(sched, n)
        time.sleep(0.3)
    sched.drain(timeout=10)
    wall = time.perf_counter() - t0
    parts = {p: _phase_s(tele, p) for p in PHASES}
    assert all(v > 0 for v in parts.values()), parts
    assert sum(parts.values()) == pytest.approx(wall, rel=0.01)
    assert _counter(tele, "serving.batches_total") == 4


def test_an_arrival_after_t_done_counts_to_wait_work(model, tele):
    sched = BatchScheduler(model, max_wait_ms=1.0).start()
    _ask(sched)
    time.sleep(0.5)
    _ask(sched)
    sched.drain(timeout=10)
    assert _counter(tele, "serving.batch_turnarounds_total") == 1
    turn = _counter(tele, "serving.batch_turnaround_seconds_total")
    assert 0 < turn < 0.25
    assert _phase_s(tele, sch.WAIT_WORK) >= 0.5


def test_with_a_queue_the_turnaround_is_the_phases_between(model, tele):
    sched = BatchScheduler(model, max_wait_ms=1.0)
    futs = [sched.submit(PROMPT, lane="batch", max_new_tokens=4)
            for _ in range(6)]           # queued before the worker starts
    sched.start()
    for f in futs:
        f.result(timeout=60)
    sched.drain(timeout=10)
    assert _counter(tele, "serving.batches_total") == 3
    assert _counter(tele, "serving.batch_turnarounds_total") == 2
    evs = tele.drain_events()
    dur = {p: [e["dur"] / 1e9 for e in evs if e["name"] == p]
           for p in PHASES}
    between = sum(dur[sch.DRAIN][:2]) + sum(dur[sch.RESPOND][:2]) \
        + sum(dur[sch.FILL][1:]) + sum(dur[sch.PREP][1:])
    turn = _counter(tele, "serving.batch_turnaround_seconds_total")
    # the spans leave out the microseconds around serving.batch's ends,
    # which the counters carry
    assert turn == pytest.approx(between, abs=2e-3)
    assert len(dur[sch.WAIT_WORK]) == 1      # the idle tail after the last


def test_a_profile_holds_the_phases_on_a_host_plane_in_order(model, tele,
                                                             tmp_path):
    import jax
    from jax.profiler import ProfileData

    sched = BatchScheduler(model, max_wait_ms=1.0).start()
    _ask(sched)                          # the worker's first batch, untraced
    jax.profiler.start_trace(str(tmp_path))
    try:
        _ask(sched)
        sched.drain(timeout=10)          # respond ends at the idle mark
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                seen.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    order = [sch.PREP, sch.LAUNCH, sch.WAIT, sch.DRAIN, sch.RESPOND]
    assert all(len(seen.get(n, ())) == 1 for n in order), sorted(seen)
    starts = [seen[n][0][0] for n in order]
    assert starts == sorted(starts)
    (b0, b1), = seen["serving.batch"]
    assert all(b0 <= seen[n][0][0] and seen[n][0][1] <= b1
               for n in order[:-1])
    assert seen[sch.RESPOND][0][0] >= b1


def test_with_telemetry_off_nothing_is_recorded_and_no_clock_read(
        model, tele, monkeypatch):
    reads = []

    def counted():
        reads.append(1)
        return time.time_ns()

    monkeypatch.setattr(tm, "time", types.SimpleNamespace(time_ns=counted))
    tele.enabled = False
    sched = BatchScheduler(model, max_wait_ms=1.0).start()
    _ask(sched, 2)
    sched.drain(timeout=10)
    assert reads == []
    assert tele.counters == {} and tele.drain_events() == []


def test_a_batch_run_in_a_chunked_prefills_yield_counts_to_the_outer(
        tele, monkeypatch):
    """The chunked-prefill yield hook runs a queued interactive batch
    inside a bulk batch's prefill: the inner batch marks nothing (its time
    is the outer's launch), and the phases still tile the worker's time."""
    from deeplearning4j_tpu.zoo.bert import Bert

    bert = Bert.tiny(causal=True, task="mlm", vocab_size=29, max_length=16,
                     hidden_dropout=0.0).init()
    model = ServingModel(bert, "ph", kind="generate", prefill_chunk=4,
                         bucketing=BucketingPolicy(batch_buckets=(1, 2),
                                                   seq_buckets=(16,)))
    model.warmup()
    t0 = time.perf_counter()
    sched = BatchScheduler(model, max_wait_ms=1.0).start()
    inner = []
    execute = model.execute

    def queue_an_interactive_one(payloads, **kw):
        if kw.get("_yield") is not None and not inner:
            inner.append(sched.submit(PROMPT, max_new_tokens=2))
        return execute(payloads, **kw)

    monkeypatch.setattr(model, "execute", queue_an_interactive_one)
    long = np.arange(1, 11, dtype=np.int32)          # three windows of 4
    assert len(sched.submit(long, lane="batch",
                            max_new_tokens=3).result(timeout=60)) == 3
    assert len(inner[0].result(timeout=60)) == 2
    time.sleep(0.3)                  # a stretch of wait_work to measure by
    sched.drain(timeout=10)
    wall = time.perf_counter() - t0
    assert _counter(tele, "serving.prefill_yield_preemptions_total") == 1
    assert _counter(tele, "serving.batches_total") == 2
    names = [e["name"] for e in tele.drain_events()]
    assert names.count(sch.PREP) == names.count(sch.RESPOND) == 1
    assert _counter(tele, "serving.batch_turnarounds_total") == 0
    parts = sum(_phase_s(tele, p) for p in PHASES)
    assert parts == pytest.approx(wall, rel=0.01)
