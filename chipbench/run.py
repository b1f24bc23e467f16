"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, checks the served tokens
against the float32 reference, and prints one JSON line as the last line
of standard output: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics read from a profiler trace of a slice of the window), ``device``
and, last, ``checks`` (each number compared with its limit, also printed
as the last lines of standard error).  Exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for, or where a
declared metric cannot be read.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        print(f"error: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"error: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"]}

    from chipbench.harness import RunError, run_cell
    from chipbench.trace import TraceError

    try:
        result = run_cell(bench, cell, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START, device=device)
    except (RunError, TraceError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    report(result)
    return 0


def report(result: dict, out=None, err=None) -> None:
    """The result line on stdout, then each check on stderr."""
    out, err = out or sys.stdout, err or sys.stderr
    print(json.dumps(result), file=out, flush=True)
    for k, v in result["checks"].items():
        print(f"check {k}: " + ", ".join(f"{a} {b}" for a, b in v.items()),
              file=err, flush=True)


if __name__ == "__main__":
    sys.exit(main())
