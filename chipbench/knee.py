"""Find an open-loop cell's knee on the chip: the highest offered rate the
system sustains without a growing backlog.

    python3 chipbench/knee.py --workload <cell> --rates 0.1 0.2 ... --seconds 60

Builds the cell's weights and lanes once, then for each rate, lowest
first, serves the cell's mix at that rate through a fresh engine over the
same lanes (a pre-roll as the mix states, then ``--seconds``) and prints
one JSON line: requests due, finished and still queued at the window's
start and end, time to first token (median, 95th percentile) and tokens
per second.  A rate whose queue grows over the window is past the knee.
The cell's traffic file then holds 0.8 of the knee as a number.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from chipbench import harness, model, serve, traffic
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import Engine
    from repro.session import Session

    if jax.devices()[0].platform != "tpu":
        print("error: the knee is found on the chip", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with open(ROOT / "BENCHMARK.json") as f:
        cell = {c["name"]: c for c in json.load(f)["workloads"]}[args.workload]
    spec = model.load_spec(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    params = model.program_params(spec, model.make_weights(spec, args.seed))
    sess = Session(model.arch_config(spec), params=params)
    calls = []
    _, runners = serve.build_engine(sess, mix, calls)
    tiers = serve.tier_specs(mix)
    print(f"set-up {time.monotonic() - T_START:.1f}s", file=sys.stderr)
    for rate in sorted(args.rates):
        m = copy.deepcopy(mix)
        m["arrivals"]["rate_per_s"] = rate
        sched = traffic.schedule(m, args.seed, args.seconds, spec.vocab)
        serve.warm(runners, m, sched)
        engine = Engine(runners, tiers)
        drv = serve.Driver(engine, sched)
        w0 = time.monotonic() + m["arrivals"]["preroll_s"]
        drv.start(w0)
        drv.run_until(w0)
        q0 = engine.scheduler.pending()
        drv.s.w0 = time.monotonic()
        drv.run_until(drv.s.w0 + args.seconds)
        drv.s.w1 = time.monotonic()
        e = harness.end_to_end(drv.s)
        due = [r for r, d in drv.s.due.items() if drv.s.w0 <= d < drv.s.w1]
        ttft = [drv.s.tokens[r][0] - drv.s.due[r] for r in due
                if drv.s.tokens[r]]
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "first_token": len(ttft),
            "finished": sum(1 for r in drv.s.submitted.values() if r.done),
            "queued_start": q0, "queued_end": engine.scheduler.pending(),
            "ttft_p50_ms": 1e3 * float(np.median(ttft)) if ttft else None,
            "ttft_p95_ms": e.get("ttft_p95_ms"),
            "itl_p95_ms": e.get("itl_p95_ms"),
            "tokens_per_s": e["tokens_per_s"]}), flush=True)
        # let the lanes drain before the next rate
        while not engine.idle:
            engine.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
