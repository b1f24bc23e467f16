"""One run of one cell: set up, pre-roll, measure, check, report.

``run.py`` checks the device and calls :func:`run_cell`; the CPU
rehearsal in ``chipbench/tests`` calls it directly at a reduced width.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from chipbench import check, model, serve, trace, traffic
from chipbench.counts import peaks

ROOT = Path(__file__).resolve().parent


class RunError(RuntimeError):
    """The run cannot report: the reason goes to stderr, the exit code is
    not 0 and no result line is printed."""


def declared(bench: dict, cell: str, section: str) -> list:
    """The metrics of ``section`` this cell reports."""
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]


def load_limits(cell: str) -> dict:
    with open(ROOT / "limits" / f"{cell}.json") as f:
        return json.load(f)


@dataclasses.dataclass
class Slice:
    """What a per-layer metric reader may read: the traced slice."""

    spec: model.Spec
    mix: dict
    peaks: dict
    window_s: float         # the slice's length, on the trace's clock
    busy_s: float           # union of device operations in the slice
    trace: trace.Trace
    calls: list             # serve.Call made in the slice
    stats: dict             # tier -> TierStats counters, slice deltas
    passes: dict            # tier -> MXU passes (0: exact)


def read_metric(name: str, sl: Slice):
    """``metrics/<name>.py``'s ``read(slice)``: a number, or None where it
    finds nothing to read."""
    path = ROOT / "metrics" / f"{name}.py"
    if not path.exists():
        raise RunError(f"per-layer metric {name!r} has no reader at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(sl)


class CompileCount:
    """Backend compiles (with their seconds) and persistent-cache loads."""

    def __init__(self):
        import jax

        self.compiles, self.seconds, self.loads = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **_):
        if name.endswith("backend_compile_duration"):
            self.compiles += 1
            self.seconds += secs
        elif "cache_retrieval" in name:
            self.loads += 1

    def __str__(self):
        return (f"{self.compiles} compiles ({self.seconds:.1f}s), "
                f"{self.loads} cache loads")


def percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def end_to_end(s: serve.Served) -> dict:
    """The window's end-to-end numbers and their sample counts."""
    w0, w1 = s.w0, s.w1
    n_tok = sum(1 for ts in s.tokens.values() for t in ts if w0 <= t <= w1)
    gaps = [b - a for ts in s.tokens.values()
            for a, b in zip(ts, ts[1:]) if w0 <= b <= w1]
    due_in = [rid for rid, d in s.due.items() if w0 <= d < w1]
    ttft = [((s.tokens[rid][0] if s.tokens[rid] and s.tokens[rid][0] <= w1
              else w1) - s.due[rid]) for rid in due_in]
    out = {"tokens": n_tok, "window_s": w1 - w0,
           "tokens_per_s": n_tok / (w1 - w0),
           "itl_samples": len(gaps), "ttft_samples": len(ttft)}
    if gaps:
        out["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
    if ttft:
        out["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
    return out


def stats_snapshot(engine) -> dict:
    return {k: dataclasses.asdict(v) for k, v in engine.lane_stats().items()}


def stats_delta(a: dict, b: dict) -> dict:
    return {t: {k: b[t][k] - a[t][k] for k in b[t]} for t in b}


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             traced: bool, *, t_start: float, device: dict, hf=None,
             backend=None, wrap=serve.SpannedRunner, control=False,
             log=sys.stderr) -> dict:
    """Run ``cell`` once; returns the result line's object."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.session import Session

    def say(msg):
        print(f"[chipbench] {msg}", file=log, flush=True)

    name = cell["name"]
    spec = model.load_spec(cell["config"], hf)
    mix = traffic.load_mix(cell["traffic"])
    limits = load_limits(name)
    section = "per_layer" if traced else "end_to_end"
    want = [m["name"] for m in declared(bench, name, section)]
    pk = peaks(device["kind"]) if traced else None

    say(f"compile cache: {enable_compile_cache()}")
    # keep every program, the engine's small eager ones too, so that a
    # run after the first loads all it runs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    count = CompileCount()
    sched = traffic.schedule(mix, seed, seconds, spec.vocab)
    say(f"{name}: {len(sched)} requests planned, prompts "
        f"{sum(p.prompt.size for p in sched)} tokens, outputs "
        f"{sum(p.max_new for p in sched)} tokens")

    # -- set-up: weights, lanes, every shape the traffic uses --------------
    t = time.monotonic()
    params = model.program_params(spec, model.make_weights(spec, seed))
    check.same_tree(spec, params)
    jax.block_until_ready(params)
    say(f"weights: {model.weight_bytes(spec):,} bytes in "
        f"{time.monotonic() - t:.2f}s")
    sess = Session(model.arch_config(spec), backend=backend, params=params)
    calls = []
    engine, runners = serve.build_engine(sess, mix, calls, wrap=wrap)
    t = time.monotonic()
    serve.warm(runners, mix, sched)
    say(f"warm-up: {time.monotonic() - t:.2f}s; so far {count}")

    # -- pre-roll: lanes busy before the window opens ----------------------
    drv = serve.Driver(engine, sched)
    arr = mix["arrivals"]
    t = time.monotonic()
    if arr["kind"] == "backlog":
        drv.start(t)
        busy = min(len(sched), mix["engine"]["slots"]
                   * len(mix["engine"]["tiers"]))
        drv.run_until(float("inf"), stop=lambda: drv.first_tokens() >= busy)
        w0 = time.monotonic()
    else:
        w0 = t + arr["preroll_s"]
        drv.start(w0)
        drv.run_until(w0)
        w0 = time.monotonic()  # the pre-roll's last step may overrun
    say(f"pre-roll: {w0 - t:.2f}s, {len(drv.s.submitted)} submitted")
    setup_s = w0 - t_start

    # -- the window --------------------------------------------------------
    drv.s.w0 = w0
    before = (count.compiles, count.loads)
    tdir, sl_stats, sl_t = None, None, None
    end = w0 + seconds
    if traced:
        sl_cfg = mix.get("trace_slice", {})
        # the slice starts once the window is in its steady mix of decode
        # and prefill (the pre-roll leaves every slot decoding at once)
        drv.run_until(w0 + min(sl_cfg.get("start_s", 0.0), seconds / 3))
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        serve.block(runners)
        n0 = len(calls)
        s0 = stats_snapshot(engine)
        jax.profiler.start_trace(tdir, profiler_options=trace.options())
        ann = jax.profiler.TraceAnnotation(trace.SLICE)
        ann.__enter__()
        a = time.monotonic()
        need = set(sl_cfg.get("until", ["decode"]))
        min_s = sl_cfg.get("min_s", 3.0)
        drv.run_until(end, stop=lambda: (
            time.monotonic() - a >= min_s
            and need <= {c.kind for c in calls[n0:]}))
        serve.block(runners)
        b = time.monotonic()
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        sl_stats = stats_delta(s0, stats_snapshot(engine))
        sl_t = (a, b, calls[n0:])
    drv.run_until(end)
    w1 = time.monotonic()
    in_window = (count.compiles - before[0], count.loads - before[1])
    drv.s.w1 = w1
    e2e = end_to_end(drv.s)
    late = drv.s.late
    say(f"window: {w1 - w0:.3f}s, {len(drv.s.submitted)} submitted, "
        f"{sum(1 for r in drv.s.submitted.values() if r.done)} finished, "
        f"{e2e['tokens']} tokens; in the window {in_window[0]} compiles, "
        f"{in_window[1]} cache loads")
    say(f"samples: itl {e2e['itl_samples']}, ttft {e2e['ttft_samples']}; "
        f"generator late p50 "
        f"{1e3 * percentile(late, 50) if late else 0.0:.1f} ms, max "
        f"{1e3 * max(late) if late else 0.0:.1f} ms")
    stats = {k: dataclasses.asdict(v) for k, v in engine.lane_stats().items()}
    say(f"lane stats: {json.dumps(stats)}")

    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))

    # -- free the program, then the reference ------------------------------
    served = [(drv.s.planned[rid], list(r.tokens), r.slot)
              for rid, r in drv.s.submitted.items() if r.done]
    attempted = len(drv.s.submitted)
    del engine, runners, sess, params, drv
    gc.collect()
    checks, correct, ctrl = check.compare(spec, seed, served, limits, mix,
                                          say, control)

    result = {"correct": bool(correct), "attempted": attempted, "failed": 0,
              "metrics": {}, "device": dict(device,
                                            memory_peak_bytes=peak)}
    units = {m["name"]: m["unit"] for m in bench[section]}
    if traced:
        try:
            a, b, sl_calls = sl_t
            tr = trace.load(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        lo, hi = tr.slice
        busy = trace.union_seconds(tr.ops, lo, hi)
        window = (hi - lo) * 1e-9   # the slice span; holds busy by its clock
        sl = Slice(spec=spec, mix=mix, peaks=pk, window_s=window,
                   busy_s=busy, trace=tr, calls=sl_calls, stats=sl_stats,
                   passes={t["name"]: serve.tier_passes(t["policy"])
                           for t in mix["engine"]["tiers"]})
        say(f"slice: {b - a:.3f}s host, {(hi - lo) * 1e-9:.3f}s traced, "
            f"busy {busy:.3f}s, {len(sl_calls)} calls, "
            f"{len(tr.within(tr.ops))} device ops")
        for m in want:
            v = read_metric(m, sl)
            if v is None:
                raise RunError(f"per-layer metric {m!r} found nothing to "
                               f"read in the traced slice")
            result["metrics"][m] = {"value": float(v), "unit": units[m]}
        result["device"].update(busy_s=busy, window_s=window)
        result["breakdown"] = trace.breakdown(tr)
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in want:
            if m not in values:
                raise RunError(f"end-to-end metric {m!r} has no samples in "
                               f"this run")
            result["metrics"][m] = {"value": float(values[m]),
                                    "unit": units[m]}
    if control:
        result["control"] = ctrl
    result["checks"] = checks
    return result

