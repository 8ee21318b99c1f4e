"""One-command autotuning sweep: measure every registered knob space and
persist equivalence-gated winners in the tuning database.

This is the harvest command for ROADMAP item 2 and the standing hardware
debt: five eras of perf work ended with "CPU proves equivalence but
cannot rank" (remat policies, kernel_impl/tile shapes, XLA flags, bucket
sets, compression_hosts). On a CPU container this script proves the
machinery end-to-end — deterministic candidate sets, every admitted
candidate equivalence-checked against the exact path, winners committed
atomically, a warm re-run measuring NOTHING; on the first real-TPU
session the SAME command sweeps the real chip and flips every deferred
default with committed evidence:

    DL4J_TPU_TUNING_DB=tuning_db python benchmarks/autotune.py

Then commit the database directory — ``auto`` dispatch and conf-time
defaulting consult it at trace time from then on (docs/AUTOTUNE.md).

Declared-but-unmeasurable spaces (xla_flags: needs subprocess isolation
— use benchmarks/fusion_sweep.py; bucket_sets: needs a recorded length
distribution; compression_hosts: needs real DCN) are listed with their
reasons, never silently skipped.

Self-test hooks (tests/test_autotune.py plants both through the driver):
``--plant-slow LABEL:SECONDS`` adds a per-call sleep to one
candidate (it must demonstrably LOSE), ``--plant-wrong LABEL`` perturbs
one candidate's outputs (the equivalence gate must REJECT it). Both act
on the real measurement path.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _parse_plants(args):
    handicap = {}
    for spec in args.plant_slow or []:
        label, _, secs = spec.rpartition(":")
        if not label:
            raise SystemExit(f"--plant-slow wants LABEL:SECONDS, got {spec!r}")
        handicap[label] = float(secs)
    corrupt = {}
    for label in args.plant_wrong or []:
        def bad(outputs, _label=label):
            import jax

            leaves, treedef = jax.tree_util.tree_flatten(outputs)
            leaves = [leaves[0] + 1.0] + leaves[1:]
            return jax.tree_util.tree_unflatten(treedef, leaves)

        corrupt[label] = bad
    return handicap, corrupt


def _tuning_counters():
    from deeplearning4j_tpu.util import telemetry as tm

    snap = tm.get_telemetry().snapshot()
    return {n: v for n, v in snap["counters"].items()
            if n.startswith("tuning.")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--db", default=None,
                    help="tuning database directory (default: "
                         "DL4J_TPU_TUNING_DB or ./tuning_db)")
    ap.add_argument("--spaces", default=None,
                    help="comma-separated space names (default: every "
                         "measurable registered space)")
    ap.add_argument("--search", choices=("grid", "random"), default="grid")
    ap.add_argument("--samples", type=int, default=6,
                    help="random-mode candidate budget per context")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--runs", type=int, default=3,
                    help="median-of-N timing runs")
    ap.add_argument("--min-window", type=float, default=0.05,
                    help="minimum timed window seconds (two-point fit)")
    ap.add_argument("--force", action="store_true",
                    help="re-measure even when the database is warm")
    ap.add_argument("--plant-slow", action="append", metavar="LABEL:SECS",
                    help="self-test: handicap one candidate per call")
    ap.add_argument("--plant-wrong", action="append", metavar="LABEL",
                    help="self-test: corrupt one candidate's outputs")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    args = ap.parse_args(argv)

    from deeplearning4j_tpu import tuning

    db_dir = args.db or tuning.database_dir() or "tuning_db"
    db = tuning.set_database(db_dir)
    driver = tuning.MeasurementDriver(
        db, search=args.search, samples=args.samples, seed=args.seed,
        runs=args.runs, min_window_s=args.min_window)
    handicap, corrupt = _parse_plants(args)

    names = ([s.strip() for s in args.spaces.split(",") if s.strip()]
             if args.spaces else tuning.measurable_spaces())
    report = {"db": db.dir, "spaces": [], "declared": []}
    failures = 0
    t_start = time.time()

    for name in names:
        space = tuning.get_space(name)
        if not space.measurable:
            report["declared"].append(
                {"space": name, "requires": space.requires,
                 "candidates": [c.label for c in space.enumerate({})]})
            continue
        for ctx in space.default_contexts():
            t0 = time.time()
            try:
                entry = driver.sweep(space, ctx, force=args.force,
                                     handicap=handicap, corrupt=corrupt)
            except RuntimeError as e:
                failures += 1
                report["spaces"].append(
                    {"space": name, "ctx": ctx, "error": str(e)})
                continue
            rows = entry.get("measured", [])
            report["spaces"].append({
                "space": name,
                "sig": space.key(ctx).sig,
                "status": entry["status"],
                "winner": entry["winner"],
                "speedup_vs_default": entry.get("speedup_vs_default"),
                "admitted": sum(1 for r in rows if r.get("admitted")),
                "rejected": sum(1 for r in rows
                                if r.get("admitted") is False),
                "sweep_seconds": round(time.time() - t0, 2),
            })

    # the remaining declared spaces always appear in the report — a
    # deferred decision is surfaced, never silently dropped
    if not args.spaces:
        for name in tuning.space_names():
            space = tuning.get_space(name)
            if not space.measurable and name not in [
                    d["space"] for d in report["declared"]]:
                report["declared"].append(
                    {"space": name, "requires": space.requires,
                     "candidates": [c.label for c in space.enumerate({})]})

    report["counters"] = _tuning_counters()
    report["db_stats"] = db.stats()
    report["wall_seconds"] = round(time.time() - t_start, 2)

    if args.json:
        print(json.dumps(report))
    else:
        import jax

        print(f"autotune: backend={jax.default_backend()} "
              f"db={db.dir} search={args.search} seed={args.seed}")
        for row in report["spaces"]:
            if "error" in row:
                print(f"  FAIL  {row['space']}: {row['error']}")
                continue
            w = row["winner"]
            print(f"  {row['status']:<9} {row['space']:<16} "
                  f"{row['sig']:<44} -> {w['label']} "
                  f"({w['ms']:.4g} ms, x{row['speedup_vs_default']:g} vs "
                  f"default; {row['admitted']} admitted, "
                  f"{row['rejected']} rejected)")
        for row in report["declared"]:
            print(f"  declared  {row['space']:<16} requires "
                  f"{row['requires']} ({len(row['candidates'])} candidates)")
        c = report["counters"]
        print(f"  counters: measurements={c.get('tuning.measurements_total', 0):g} "
              f"lookups={c.get('tuning.lookups_total', 0):g} "
              f"hits={c.get('tuning.hits_total', 0):g} "
              f"equivalence_rejects={c.get('tuning.equivalence_rejects_total', 0):g} "
              f"backend_rejects={c.get('tuning.backend_rejects_total', 0):g}")
        print(f"  db: {report['db_stats']['entries']} entries in "
              f"{report['db_stats']['dir']} "
              f"({report['wall_seconds']}s total)")
        if jax.default_backend() != "tpu":
            print(f"  NOTE: no TPU — winners rank the "
                  f"{jax.default_backend()} backend only (entries key "
                  "backend+topology) and no TPU dispatch will consult "
                  "them; run this command on the chip to rank the kernels "
                  "there (docs/AUTOTUNE.md).")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
