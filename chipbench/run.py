"""One run of one cell:

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses to run without a TPU (exit 2, no result). Prints the device it ran
on and, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``compared``: every number that decided ``correct``
beside its limit (the same go to standard error as its last lines).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # before anything heavy is imported

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from chipbench.manifest import (ROOT, Cell, load_manifest,  # noqa: E402
                                module_from)

OUT_DIR = os.path.join(ROOT, ".chipbench_out")


class Tracer:
    """With ``--trace 1``: profiles ``trace_seconds`` of the window, from
    ``trace_after_s`` in, under a ``cb:window`` annotation. The owner of the
    window's thread calls ``poll()`` as it goes."""

    def __init__(self, on: bool, logdir: str, after_s: float, for_s: float):
        self.on, self.logdir = on, logdir
        self.after_s, self.for_s = after_s, for_s
        self.t0 = self.t_on = self.window = None
        self.done = False

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def poll(self):
        if not self.on or self.done:
            return
        import jax

        now = time.perf_counter()
        if self.t_on is None:
            if now - self.t0 >= self.after_s:
                shutil.rmtree(self.logdir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(self.logdir, profiler_options=opts)
                self.window = jax.profiler.TraceAnnotation("cb:window")
                self.window.__enter__()
                self.t_on = time.perf_counter()
        elif now - self.t_on >= self.for_s:
            self._stop()

    def _stop(self):
        import jax

        self.window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.done = True

    def __exit__(self, *exc):
        if self.on and self.t_on is not None and not self.done:
            self._stop()
        return False


class Context:
    """What a traffic kind's driver is handed."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool):
        self.cell, self.seed, self.seconds, self.trace = (cell, seed,
                                                          seconds, trace)
        self.setup_s = None
        self.trace_dir = os.path.join(OUT_DIR, "trace", cell.name)
        self._compiles_at_setup = self._compiles_at_close = None

    def mark_setup_done(self):
        from deeplearning4j_tpu.util import get_watcher

        self.setup_s = time.perf_counter() - T_PROCESS
        self._compiles_at_setup = dict(get_watcher().counts())
        print(f"chipbench: set-up took {self.setup_s:.1f} s; compiles "
              f"{self._compiles_at_setup}", file=sys.stderr, flush=True)

    def mark_window_done(self):
        """The first call counts: what compiles after the close (the look
        at the step's memory, the reference) is not the window's."""
        from deeplearning4j_tpu.util import get_watcher

        if self._compiles_at_close is None:
            self._compiles_at_close = dict(get_watcher().counts())

    def tracer(self) -> Tracer:
        mix = self.cell.mix
        return Tracer(self.trace, self.trace_dir,
                      float(mix.get("trace_after_s", 1.0)),
                      float(mix.get("trace_seconds", 6.0)))

    def memory_peak_bytes(self, step_memory=None) -> int:
        """Read when the window closes, before the reference runs: the peak
        of live arrays on the fullest chip as JAX's allocator counts it.
        That count leaves out what a compiled program reserves for itself
        while it runs, which is most of a train step's footprint, so a
        driver that can name its step hands in the step's
        ``memory_analysis()``: live arrays at the close (the step's
        arguments among them) + its temporaries + what it returns beside
        the buffers it was given."""
        import jax

        self.mark_window_done()
        stats = [d.memory_stats() or {}
                 for d in jax.devices()[:self.cell.chips]]
        print(f"chipbench: memory_stats of device 0: {stats[0]}",
              file=sys.stderr)
        peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
        if step_memory:
            print(f"chipbench: the step's memory_analysis: {step_memory}",
                  file=sys.stderr)
            live = max(int(s.get("bytes_in_use", 0)) for s in stats)
            peak = max(peak, live + step_memory["temp_bytes"]
                       + step_memory["output_bytes"]
                       - step_memory["alias_bytes"])
        return peak

    def compile_counts(self) -> dict:
        now, then = self._compiles_at_close, self._compiles_at_setup
        keys = ("backend_compiles", "persistent_cache_hits",
                "uncached_compiles")
        return {"setup": {k: then[k] for k in keys},
                "window": {k: now[k] - then[k] for k in keys}}


def find_devices(chips: int) -> dict:
    """What JAX found; exits 2 unless that is a TPU with the chips the cell
    asks for. There is no fallback to the CPU."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"chipbench: jax {jax.__version__} platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}",
          file=sys.stderr, flush=True)
    if device["platform"] != "tpu" or device["count"] < chips:
        print(f"chipbench: needs {chips} TPU chip(s); no result",
              file=sys.stderr)
        raise SystemExit(2)
    return device


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            device: dict, planted=None) -> dict:
    """Everything of a run after the look for a chip; returns the result
    object. ``planted`` is handed to the driver (tests only)."""
    import jax

    from deeplearning4j_tpu.util import get_watcher
    from deeplearning4j_tpu.util.compile_cache import enable_persistent_cache

    from chipbench import checks, peaks

    enable_persistent_cache()
    get_watcher()                    # hooks in before the first compile
    if cell.cfg.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          cell.cfg["matmul_precision"])
    ctx = Context(cell, seed, seconds, trace)
    driver = module_from("kinds", cell.mix["kind"])
    out = driver.run(ctx, planted=planted)

    run = out["run"]
    run.update(cfg=cell.cfg, mix=cell.mix, chips=cell.chips,
               peaks=peaks.peaks_of(device["kind"]),
               compiles=ctx.compile_counts(), setup_s=ctx.setup_s,
               end_to_end=out["end_to_end"], trace=None)
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": checks.verdict(out["numbers"]),
              "attempted": out["attempted"], "failed": out["failed"]}
    if trace:
        from chipbench import trace as tr

        run["trace"] = tr.reduce_logdir(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        dev.update(busy_s=run["trace"]["busy_s"],
                   window_s=run["trace"]["window_s"])
        metrics = {}
        for m in cell.per_layer():
            value = module_from("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result.update(metrics=metrics, device=dev,
                      breakdown=run["trace"]["breakdown"])
    else:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        result.update(metrics={
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end() if m["name"] in values}, device=dev)
    result["info"] = {"setup_s": ctx.setup_s, **out["end_to_end"],
                      "compiles_in_window": run["compiles"]["window"]}
    result["compared"] = {n["name"]: {"value": float(n["value"]),
                                      "limit": float(n["limit"])}
                          for n in out["numbers"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(load_manifest(), args.workload)
    try:
        import deeplearning4j_tpu  # noqa: F401
    except ImportError as e:
        print(f"chipbench: the program is not in this checkout ({e}); "
              "no result", file=sys.stderr)
        return 3
    device = find_devices(cell.chips)
    result = measure(cell, args.seed, args.seconds, bool(args.trace), device)
    for name, n in result["compared"].items():
        print(f"chipbench: compared {name} = {n['value']:.6g} "
              f"(limit {n['limit']:.6g})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
