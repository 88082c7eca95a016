"""Benchmark entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (assignment contract).

    PYTHONPATH=src python -m benchmarks.run             # full suite
    PYTHONPATH=src python -m benchmarks.run --quick     # fewer seeds
    PYTHONPATH=src python -m benchmarks.run --only fig5
    PYTHONPATH=src python -m benchmarks.run --only roofline   # opt-in

A section that raises is reported as ``<name>_FAILED`` and the run goes
on to the next one, but the command then exits 1.  ``roofline`` reads
the artifacts of a ``repro.launch.dryrun`` run (``results/dryrun_final``)
and runs only as ``--only roofline``.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer seeds")
    ap.add_argument("--seeds", type=int, default=None,
                    help="paper uses 40; default 10 (3 with --quick)")
    ap.add_argument("--only", default=None,
                    help="substring filter on section names")
    args = ap.parse_args()
    seeds = args.seeds or (3 if args.quick else 10)

    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    sections = []

    from benchmarks import fleetsim_bench, netsim_bench, orchestrator_bench, \
        paper_tables, queue_bench, roofline_report, serving_bench
    sections.append(("fig5_fig6", lambda: paper_tables.fig5_fig6(seeds)))
    sections.append(("ablations",
                     lambda: paper_tables.ablations(max(3, seeds // 2))))
    sections.append(("queue_microbench", lambda: queue_bench.run(
        depths=(100, 1000) if args.quick else (100, 1000, 4000))))
    sections.append(("orchestrator_throughput", lambda: orchestrator_bench.run(
        seeds=(0,) if args.quick else (0, 1))))
    # full runs refresh the committed BENCH_fleetsim.json baseline
    sections.append(("fleetsim_throughput", lambda: fleetsim_bench.run(
        smoke=args.quick,
        json_path=None if args.quick else fleetsim_bench.JSON_DEFAULT)))
    # full runs refresh the committed BENCH_netsim.json baseline
    sections.append(("netsim_sweep", lambda: netsim_bench.run(
        smoke=args.quick,
        json_path=None if args.quick else netsim_bench.JSON_DEFAULT)))
    sections.append(("serving_engine", lambda: serving_bench.run(
        n_requests=30 if args.quick else 60)))
    if args.only == "roofline":     # needs a repro.launch.dryrun run first
        sections.append(("roofline", lambda: roofline_report.table(
            "results/dryrun_final")))

    print("name,us_per_call,derived")
    failed = []
    for name, fn in sections:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            for row in fn():
                n, us, derived = row
                print(f"{n},{us:.2f},{derived}")
        except Exception as e:   # keep the suite going; fail at the end
            failed.append(name)
            print(f"{name}_FAILED,0,{type(e).__name__}: {e}", file=sys.stderr)
            print(f"{name}_FAILED,0,{type(e).__name__}")
        print(f"# section {name} took {time.time() - t0:.1f}s",
              file=sys.stderr)
    if failed:
        sys.exit(f"failed sections: {', '.join(failed)}")


if __name__ == "__main__":
    main()
