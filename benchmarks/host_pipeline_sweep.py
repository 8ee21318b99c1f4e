"""Host-pipeline A/B sweep: sync vs device-prefetch vs prefetch + multiproc ETL.

PR 1 attacked the device half of the step budget (fusion_sweep.py); this
harness measures the HOST half that ISSUE 2 builds: an injectable
slow-transform load is fed to the LeNet-5 train loop three ways —

  sync            batches transformed + staged in the fit() thread (the
                  pre-ISSUE-2 path): every step pays compute + ETL serially
  prefetch        AsyncDataSetIterator double-buffers ETL + device_put of
                  batch k+1 under batch k's compute (sync_every coalescing on)
  prefetch+mpetl  TransformProcess records ETL'd by the multiprocess
                  executor first (DL4J_TPU_ETL_WORKERS / --workers), then
                  prefetch-fed — the full ISSUE-2 pipeline

Methodology: every per-batch cost is a TWO-POINT FIT — wall(n_hi batches)
− wall(n_lo batches) over (n_hi − n_lo) — which cancels the pipeline ramp
(first batch waits on the first transform) and any fixed setup, the same
cancellation fusion_sweep.py uses for the fixed per-call cost. Each
candidate is median-of-3 fits with the spread as ``noise``.

ETL load is injectable: ``--etl-ms`` per batch (default 0.8x the measured
compute step — heavy enough that sync pays ~1.8-2x, light enough to be
hideable) and ``--etl-load sleep|spin``. ``sleep`` models I/O-shaped ETL
(decode waits, network reads) and can overlap even on this 1-core host;
``spin`` models CPU-bound transforms, which a 1-core host CANNOT overlap —
running both makes the measurement ceiling explicit (docs/HOST_PIPELINE.md).

Usage::

    python benchmarks/host_pipeline_sweep.py                 # auto-sized
    python benchmarks/host_pipeline_sweep.py --etl-load spin # 1-core ceiling
    python benchmarks/host_pipeline_sweep.py --json sweep.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# runnable as `python benchmarks/host_pipeline_sweep.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _build_lenet, _med3  # noqa: E402


def _load_fn(kind: str, seconds: float):
    if kind == "sleep":
        return lambda: time.sleep(seconds)

    def spin():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            pass

    return spin


class _SlowArrayIterator:
    """n batches of (x, y) with the injected per-batch ETL load applied in
    whatever thread iterates — fit()'s own thread on the sync leg, the
    prefetch worker on the async legs."""

    def __init__(self, x, y, batch, n_batches, load):
        self.x, self.y, self.batch, self.n, self.load = x, y, batch, n_batches, load

    def __iter__(self):
        from deeplearning4j_tpu.data import DataSet

        for i in range(self.n):
            self.load()
            j = (i * self.batch) % len(self.x)
            yield DataSet(self.x[j:j + self.batch], self.y[j:j + self.batch])

    def reset(self):
        pass

    def batch_size(self):
        return self.batch


class _RecordsIterator:
    """Transformed flat records → NHWC DataSet batches (the merge-back half
    of the multiprocess ETL leg)."""

    def __init__(self, records, batch, image_hw=28, num_classes=10):
        self.records, self.batch = records, batch
        self.hw, self.nc = image_hw, num_classes

    def __iter__(self):
        from deeplearning4j_tpu.data import DataSet

        for i in range(0, len(self.records), self.batch):
            chunk = self.records[i:i + self.batch]
            x = np.asarray([r[:-1] for r in chunk], np.float32).reshape(
                len(chunk), self.hw, self.hw, 1)
            y = np.eye(self.nc, dtype=np.float32)[
                np.asarray([int(r[-1]) for r in chunk])]
            yield DataSet(x, y)

    def reset(self):
        pass

    def batch_size(self):
        return self.batch


def _records(x, y, n_batches, batch):
    n = n_batches * batch
    flat = x[:n].reshape(n, -1)
    labels = np.argmax(y[:n], axis=1)
    return [list(map(float, flat[i])) + [int(labels[i])] for i in range(n)]


def _slow_tp(per_record_load):
    """TransformProcess with the injected load on one column — the
    'serialized transform' the worker processes apply."""
    from deeplearning4j_tpu.datavec import Schema, TransformProcess

    schema = Schema.builder().add_column_double("px0").build()  # probed col

    def loaded(v):
        per_record_load()
        return v

    # schema handling in this harness is positional: only column 0 is
    # declared/transformed, the rest pass through untouched
    return (TransformProcess.builder(schema)
            .double_column_transform("px0", loaded).build())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--n-lo", type=int, default=8)
    ap.add_argument("--n-hi", type=int, default=24)
    ap.add_argument("--etl-ms", type=float, default=None,
                    help="injected ETL per batch (default 0.8x measured step)")
    ap.add_argument("--etl-load", choices=("sleep", "spin"), default="sleep")
    ap.add_argument("--workers", type=int, default=None,
                    help="multiprocess ETL workers (default env/auto)")
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args()

    import jax

    from deeplearning4j_tpu.data import AsyncDataSetIterator
    from deeplearning4j_tpu.datavec import MultiProcessTransformExecutor

    net = _build_lenet(sync_every=4)

    class _Observer:  # coalesced dispatch only runs with a listener
        def iteration_done(self, model, iteration, epoch):
            pass

    net.set_listeners(_Observer())
    rng = np.random.default_rng(0)
    x = rng.normal(size=(args.batch * args.n_hi, 28, 28, 1)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, len(x))]
    xd, yd = jax.device_put(x[:args.batch]), jax.device_put(y[:args.batch])
    for _ in range(4):
        net._fit_batch(xd, yd)
    float(net.score_value)

    def compute_wall(n):
        t0 = time.perf_counter()
        for _ in range(n):
            net._fit_batch(xd, yd)
        float(net.score_value)
        return time.perf_counter() - t0

    def fit_wall(make_iter, n):
        it = make_iter(n)
        t0 = time.perf_counter()
        net.fit(it, epochs=1)
        float(net.score_value)
        return time.perf_counter() - t0

    def slope(wall_at):
        """Two-point fit: per-batch cost with ramp/fixed costs cancelled."""
        def one():
            w_lo = wall_at(args.n_lo)
            w_hi = wall_at(args.n_hi)
            return (w_hi - w_lo) / (args.n_hi - args.n_lo)
        return _med3(one)

    step_s, step_noise = slope(compute_wall)
    etl_s = (args.etl_ms / 1e3) if args.etl_ms else 0.8 * step_s
    batch_load = _load_fn(args.etl_load, etl_s)
    per_record_load = _load_fn(args.etl_load, etl_s / args.batch)

    def slow_iter(n):
        return _SlowArrayIterator(x, y, args.batch, n, batch_load)

    rows = [{"candidate": "compute_only", "ms_per_batch": step_s * 1e3,
             "noise": step_noise, "ratio": 1.0}]

    legs = [
        ("sync", lambda n: fit_wall(slow_iter, n)),
        ("prefetch", lambda n: fit_wall(
            lambda m: AsyncDataSetIterator(slow_iter(m), buffer_size=2), n)),
    ]
    for name, wall_at in legs:
        s, nz = slope(wall_at)
        rows.append({"candidate": name, "ms_per_batch": s * 1e3, "noise": nz,
                     "ratio": s / step_s})

    # -- multiprocess ETL leg: transform wall (serial vs N workers) + the
    # end-to-end prefetch fit over the transformed records -----------------
    tp = _slow_tp(per_record_load)
    recs = _records(x, y, args.n_hi, args.batch)
    ex = MultiProcessTransformExecutor(tp, num_workers=args.workers,
                                       min_records_per_worker=8)
    outs = {}  # last output of each timed leg — compared below, not re-run

    def timed_into(key, fn):
        t0 = time.perf_counter()
        outs[key] = fn()
        return time.perf_counter() - t0

    t_serial, nz_s = _med3(lambda: timed_into("serial", lambda: tp.execute(recs)))
    t_mp, nz_m = _med3(lambda: timed_into("mp", lambda: ex.execute(recs)))
    if outs["mp"] != outs["serial"]:  # survives python -O, unlike assert
        raise RuntimeError("multiprocess ETL output != serial output")
    rows.append({"candidate": f"etl_serial ({len(recs)} records)",
                 "ms_per_batch": t_serial * 1e3 / args.n_hi, "noise": nz_s,
                 "ratio": None})
    rows.append({"candidate": f"etl_mp x{ex.num_workers}",
                 "ms_per_batch": t_mp * 1e3 / args.n_hi, "noise": nz_m,
                 "ratio": None, "etl_speedup": t_serial / t_mp})

    def mpetl_prefetch_wall(n):
        sub = recs[:n * args.batch]
        t0 = time.perf_counter()
        out = ex.execute(sub)
        net.fit(AsyncDataSetIterator(_RecordsIterator(out, args.batch),
                                     buffer_size=2), epochs=1)
        float(net.score_value)
        return time.perf_counter() - t0

    s, nz = slope(mpetl_prefetch_wall)
    rows.append({"candidate": "prefetch+mpetl", "ms_per_batch": s * 1e3,
                 "noise": nz, "ratio": s / step_s})

    result = {
        "config": {"batch": args.batch, "n_lo": args.n_lo, "n_hi": args.n_hi,
                   "etl_ms_per_batch": round(etl_s * 1e3, 3),
                   "etl_load": args.etl_load, "workers": ex.num_workers,
                   "host_cores": os.cpu_count(),
                   "platform": jax.default_backend()},
        "candidates": rows,
    }
    print(f"\nhost-pipeline sweep (two-point fit {args.n_lo}->{args.n_hi} "
          f"batches, median-of-3; ETL {args.etl_load} "
          f"{etl_s * 1e3:.1f} ms/batch; {os.cpu_count()}-core host)")
    print(f"{'candidate':<28} {'ms/batch':>9} {'noise':>8} {'x compute':>10}")
    for r in rows:
        ratio = "" if r["ratio"] is None else f"{r['ratio']:.3f}"
        extra = (f"  (speedup {r['etl_speedup']:.2f}x)"
                 if "etl_speedup" in r else "")
        noise = r["noise"].split(" ")[0]  # full string stays in the JSON
        print(f"{r['candidate']:<28} {r['ms_per_batch']:>9.2f} "
              f"{noise:>8} {ratio:>10}{extra}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=2)
        print(f"\nwrote {args.json}")


if __name__ == "__main__":
    main()
