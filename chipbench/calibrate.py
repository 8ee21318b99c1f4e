"""Readings for the limits of ``correct``, taken on the chip at a cell's own
size, many seeds in one process:

    python -m chipbench.calibrate --workload <name> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--seconds 8]

For every seed it prints the program's numbers against the plain reference
(the lower readings); for the control seeds also the control's (the
reference computed one precision down, put in the program's place) and, for
a training cell, the half-batch fault's. Every row carries ``correct``, the
harness's own verdict (``checks.verdict``) on that row under the limits in
the configuration's file, and ``over``, the numbers that fail it: a control
or a fault has to come out not correct there. The limits were set from such
a listing; PERF.md keeps the readings. ``--sweep`` finds an open loop's
knee. A ``--cfg KEY=JSON`` override (the look) is written into every row.
Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

from chipbench.manifest import Cell, load_manifest, module_from


_OVERRIDES: dict = {}     # --cfg KEY=JSON of this call; every row carries them


def _print(row: dict) -> None:
    if _OVERRIDES:
        row = {**row, "overrides": _OVERRIDES}
    print(json.dumps(row), flush=True)


def _show(tag: str, seed: int, numbers: list, **more) -> None:
    """One row: who, the seed, the harness's own verdict on these numbers
    under the configuration's limits with the numbers that fail it, then
    every number."""
    from chipbench import checks

    over = [n["name"] for n in numbers
            if not n["value"] <= n["limit"]]
    _print({"who": tag, "seed": seed, "correct": checks.verdict(numbers),
            "over": over, **more,
            **{n["name"]: float(n["value"]) for n in numbers}})


def _both(got: dict, want: dict) -> dict:
    """Worst-leaf and median-leaf readings side by side."""
    from chipbench import checks

    return {f"{key}_{tag}": how(got[f"{key}_norms"], want[f"{key}_norms"])
            for key in ("grad", "change")
            for tag, how in (("worst", checks.worst_leaf_gap),
                             ("median", checks.median_leaf_gap))}


def _angles(got: dict, want: dict) -> dict:
    """1 - cosine of every kept leaf's first gradient against the
    reference's: what a limit under ``grad_angle`` is chosen from."""
    from chipbench import checks

    return {k: round(checks.angle(got["first_grads"][k], w), 6)
            for k, w in want["first_grads"].items()}


def _gaps(got: dict, want: dict, key: str) -> dict:
    """Every leaf's gap of ``key``'s norms, as ``checks.leaf_gaps`` takes
    it: the look, and what ``big_leaf_size`` and its limit are chosen
    from."""
    from chipbench import checks

    return dict(zip(want[key], (round(g, 6) for g in checks.leaf_gaps(
        got[key], want[key]))))


def training(cell: Cell, seeds, control_seeds) -> None:
    """For every seed the program against the reference; for the control
    seeds also the control and the half-batch fault, each the reference put
    in the program's place. Rows of every leaf's gradient angle and gaps of
    norms follow each: the leaves to compare are chosen from those."""
    from chipbench import checks, train

    cfg = cell.cfg
    for seed in seeds:
        prog = train.Program(cfg, cell.mix, seed, cell.chips, keep="all")
        got = prog.first_steps()
        prog.free()
        want = train.follow_reference(prog)
        rows = [("program", got)]
        if seed in control_seeds:
            rows += [("control", train.follow_reference(
                prog, lower=cfg["control"])),
                ("fault_half_batch", train.follow_reference(
                    prog, fault="half_batch"))]
        for tag, other in rows:
            _show(tag, seed,
                  checks.training_numbers(other, want, cfg["limits"]),
                  **_both(other, want))
            _print({"who": tag + "_angles", "seed": seed,
                    **_angles(other, want)})
            for key in ("grad", "change"):
                _print({"who": f"{tag}_{key}_gaps", "seed": seed,
                        **_gaps(other, want, key + "_norms")})
        del prog, got, want, rows
        gc.collect()


def serving(cell: Cell, seeds, control_seeds, seconds: float) -> None:
    """One server for all the seeds: each seed's weights are put into the
    running model, a short window at the cell's own load is driven, and the
    finished requests are judged. A seed's weights are let go of before
    the next are made: two sets of a large configuration do not fit a
    chip."""
    import jax.numpy as jnp

    from chipbench import serve, traffic

    cfg, mix = cell.cfg, cell.mix
    ref = module_from("reference", cfg["reference"])
    builder = module_from("builders", cfg["builder"])
    weights = ref.make_weights(seeds[0], cfg)
    server, model = serve.start_server(cfg, mix, weights, builder)
    try:
        for k, seed in enumerate(seeds):
            if k:
                weights = None
                model.net.params = [None] * len(model.net.params)
                gc.collect()
                weights = ref.make_weights(seed, cfg)
                builder.load(model.net, weights)
            reqs = traffic.requests(mix, cfg, seed, seconds)
            got = serve.drive(server.url, mix, reqs, mix["kind"], seconds)
            ok = [r for r in got["rows"] if r["status"] == 200]
            _show("program", seed,
                  serve.judge(ref, weights, cfg, mix, seed, ok),
                  tokens_per_s=sum(
                      len(r["prompt"]) + len(r["tokens"]) for r in ok
                      if r["done"] <= seconds) / seconds)
            if seed in control_seeds:
                _show("control", seed, serve.judge(
                    ref, weights, cfg, mix, seed, ok,
                    control_dtype=jnp.dtype(cfg["control"])))
    finally:
        server.stop()


def sweep(cell: Cell, rates, seconds: float, seed: int) -> None:
    """The knee: one server, one open-loop window at each fixed rate. The
    queue grows where the second half of the window waits longer than the
    first and the completions fall behind the arrivals."""
    import numpy as np

    from chipbench import serve, traffic

    cfg, mix = cell.cfg, dict(cell.mix)
    ref = module_from("reference", cfg["reference"])
    builder = module_from("builders", cfg["builder"])
    weights = ref.make_weights(seed, cfg)
    server, _model = serve.start_server(cfg, mix, weights, builder)
    try:
        for rate in rates:
            mix["rate_per_s"] = rate
            reqs = traffic.requests(mix, cfg, seed, seconds)
            got = serve.drive(server.url, mix, reqs, "open_loop", seconds)
            ok = [r for r in got["rows"] if r["status"] == 200]
            lat = np.array([r["done"] - r["due"] for r in ok])
            due = np.array([r["due"] for r in ok])
            half = due < seconds / 2
            c = got["counters"]
            _print({
                "who": "sweep", "rate": rate, "sent": len(reqs),
                "ok": len(ok),
                "done_in_window_per_s": float(np.sum(
                    np.array([r["done"] for r in ok]) <= seconds) / seconds),
                "p50": float(np.quantile(lat, 0.5)),
                "p90": float(np.quantile(lat, 0.9)),
                "mean_first_half": float(lat[half].mean()),
                "mean_second_half": float(lat[~half].mean()),
                "last_done": float(max(r["done"] for r in ok)),
                "batches": c.get("dl4j_serving_batches_total"),
                "occupancy": c.get("dl4j_serving_batch_occupancy_sum", 0)
                / max(1.0, c.get("dl4j_serving_batch_occupancy_count", 0)),
            })
    finally:
        server.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.calibrate")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sweep", default="", help="rates, comma-separated")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--cfg", action="append", default=[], metavar="KEY=JSON",
                    help="override a key of the configuration (the look); "
                    "every row then says so")
    args = ap.parse_args(argv)
    from chipbench import run

    cell = Cell(load_manifest(), args.workload)
    for item in args.cfg:
        key, _, value = item.partition("=")
        cell.cfg[key] = _OVERRIDES[key] = json.loads(value)
    run.find_devices(cell.chips)
    import jax

    from deeplearning4j_tpu.util.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    if cell.cfg.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          cell.cfg["matmul_precision"])
    seeds = [int(s) for s in args.seeds.split(",")]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    if args.sweep:
        sweep(cell, [float(r) for r in args.sweep.split(",")], args.seconds,
              seeds[0])
    elif module_from("kinds", cell.mix["kind"]).family == "training":
        training(cell, seeds, control)
    else:
        serving(cell, seeds, control, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
