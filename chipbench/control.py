"""Read the comparison's two ends on the chip, at a cell's own size.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]
    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds <n> --fault <name>

For each seed, in this one process: one run of the cell as ``run.py``
makes it (set-up, pre-roll, a window of ``--seconds``), then the float32
reference over the sampled finished requests, read twice at the same
positions: the gap of each token the program served (the lower reading,
and the program's verdict) and the gap of the token the reference puts
first when every weight matmul takes fp8 operands (the control, the upper
reading, put through the same check for its own verdict).  With
``--fault`` the runner under the engine is broken as
``chipbench/faults.py`` names, and no control is read.  One JSON line per
seed, then a summary.  Exits 0 only where every program verdict is
``correct`` and every control verdict is not (with ``--fault``: where no
run is ``correct``).  The benchmark's own runs never run the control.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    import jax

    from chipbench import serve
    from chipbench.faults import FAULTS
    from chipbench.harness import run_cell

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = {c["name"]: c for c in bench["workloads"]}[args.workload]
    d = jax.devices()
    if d[0].platform != "tpu":
        print("error: the control is read on the chip", file=sys.stderr)
        return 2
    device = {"platform": d[0].platform, "kind": d[0].device_kind,
              "count": cell["chips"]}
    wrap = FAULTS[args.fault] if args.fault else serve.SpannedRunner
    lines = []
    for seed in args.seeds:
        r = run_cell(bench, cell, seed, args.seconds, False,
                     t_start=time.monotonic(), device=device, wrap=wrap,
                     control=args.fault is None)
        c = r["checks"]
        line = {"seed": seed, "fault": args.fault,
                "program": c["logit_gap_max_sd"]["value"],
                "tokens": c["tokens_compared"]["value"],
                "correct": r["correct"]}
        if "control" in r:
            line["control"] = r["control"]["logit_gap_max_sd"]
            line["control_correct"] = r["control"]["correct"]
        print(json.dumps(line), flush=True)
        lines.append(line)
    prog = [x["program"] for x in lines if x["program"] is not None]
    summary = {"workload": args.workload, "fault": args.fault,
               "program_max": max(prog, default=None),
               "program_correct": [x["correct"] for x in lines]}
    if args.fault is None:
        summary["control_min"] = min(
            (x["control"] for x in lines if x["control"] is not None),
            default=None)
        summary["control_correct"] = [x["control_correct"] for x in lines]
        ok = (all(summary["program_correct"])
              and not any(summary["control_correct"]))
    else:
        ok = not any(summary["program_correct"])
    print(json.dumps(summary), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
