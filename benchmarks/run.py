"""Benchmark driver — one section per paper table + kernels + roofline.

Canonical invocation (from the repo root; ``benchmarks/__init__.py`` makes
``src/repro`` importable on its own):

    python -m benchmarks.run [--json [PATH]] [--fast] [--skip-resnet]

``--json`` writes the versioned ``BENCH_*.json`` perf-trajectory artifact
(default path ``BENCH_<host>.json``); ``tools/check_bench.py`` diffs it
against the committed baseline.  A ``name,value,unit,derived`` CSV summary
is printed at the end (legacy stdout contract).
"""
import argparse
import os
import socket
import sys

if __package__ in (None, ""):  # executed as a script: python benchmarks/run.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="run the benchmark suite and (optionally) emit the "
                    "BENCH_*.json perf-trajectory artifact")
    ap.add_argument("--skip-resnet", action="store_true",
                    help="skip the (slow) Table IV ResNet benchmark")
    ap.add_argument("--resnet-steps", type=int, default=120)
    ap.add_argument("--fast", action="store_true",
                    help="CI subset: fewer timing iterations and smaller "
                         "problem sizes (recorded in the artifact meta)")
    ap.add_argument("--iters", type=int, default=None,
                    help="override the per-metric timing iteration count")
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="write the BENCH_*.json artifact here "
                         "(default: BENCH_<host>.json in the cwd)")
    ap.add_argument("--tune", default=None, metavar="TUNE_JSON",
                    help="measured kernel-tuning artifact to activate for "
                         "the whole run (kernels/TUNE_<device>.json; "
                         "generate with python -m benchmarks.autotune). "
                         "Default: the REPRO_TUNE_FILE env var if set, "
                         "else the static tuning tables")
    args = ap.parse_args(argv)

    from benchmarks import (bench_kernels, bench_serving, real_accuracy,
                            roofline, table2_ppa, table3_image)
    from benchmarks.harness import BenchReport, activate_tuning
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    table = activate_tuning(args.tune)
    if table is not None:
        from repro.kernels import autotune

        print(f"[bench] tuned kernel table active: "
              f"{autotune.active_source()} ({len(table.entries)} entries, "
              f"device {table.device})")
    report = BenchReport(fast=args.fast, iters=args.iters)
    table2_ppa.run(report)
    table3_image.run(report)
    real_accuracy.run(report)
    bench_kernels.run(report)
    roofline.run(report)
    bench_serving.run(report)
    if not args.skip_resnet:
        from benchmarks import table4_resnet

        table4_resnet.run(report, train_steps=args.resnet_steps)

    print("\nname,value,unit,derived")
    for name, value, unit, derived in report.csv_rows():
        print(f"{name},{value:.1f},{unit},{derived}")

    if args.json is not None:
        path = args.json or f"BENCH_{socket.gethostname()}.json"
        report.write(path)
        print(f"\n[bench] wrote {path} ({len(report.metrics)} metrics, "
              f"schema {report.to_dict()['schema']}); gate with: "
              f"python tools/check_bench.py --baseline "
              f"benchmarks/BENCH_cpu_ci.json {path}")


if __name__ == "__main__":
    main()
