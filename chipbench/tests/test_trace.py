"""The reduction from a chip trace to metrics, on a recorded trace.

``data/small.xplane.pb.gz`` was recorded on one TPU v5e: a 2-layer,
64-wide qwen3-shaped model on a premium (exact, XLA) and a bulk
(segmented1, Pallas) lane, traced over a few engine steps by the
harness's own slice.  ``data/small.json`` holds what the harness knew of
that slice: its calls into the lanes, the engine's counters, its length
and the metric values it read.
"""
import dataclasses
import json
from pathlib import Path

import pytest

from chipbench import counts, harness, model, serve, trace

DATA = Path(__file__).resolve().parent / "data"
READERS = ["device_idle_share", "decode_step_ms", "prefill_chunk_ms",
           "decode_rows_mean", "serve_mfu", "afpm_matmul_roofline"]


@pytest.fixture(scope="module")
def side():
    return json.loads((DATA / "small.json").read_text())


@pytest.fixture(scope="module")
def tr():
    return trace.load_bytes(str(DATA / "small.xplane.pb.gz"))


def make_slice(side, tr, **kw):
    spec = model.load_spec("qwen3-4b", side["hf"])
    lo, hi = tr.slice
    fields = dict(spec=spec, mix=side["mix"],
                  peaks=counts.peaks("TPU v5 lite"),
                  window_s=side["window_s"],
                  busy_s=trace.union_seconds(tr.ops, lo, hi), trace=tr,
                  calls=[serve.Call(**{**c, "ctx": tuple(c["ctx"])})
                         for c in side["calls"]],
                  stats=side["stats"], passes=side["passes"])
    fields.update(kw)
    return harness.Slice(**fields)


def brute_union(evs, lo, hi):
    """Union length by sweeping every boundary (independent of
    ``trace.union_seconds``'s merge)."""
    pts = sorted({lo, hi} | {min(max(t, lo), hi) for e in evs
                             for t in (e.t0, e.t1)})
    total = 0.0
    for a, b in zip(pts, pts[1:]):
        mid = (a + b) / 2
        if any(e.t0 <= mid < e.t1 for e in evs):
            total += b - a
    return total * 1e-9


def test_busy_is_the_union_of_intervals(tr):
    lo, hi = tr.slice
    ops = tr.within(tr.ops)
    busy = trace.union_seconds(tr.ops, lo, hi)
    assert busy == pytest.approx(brute_union(ops, lo, hi), rel=1e-9)
    assert 0 < busy <= (hi - lo) * 1e-9
    assert busy <= sum(e.dur for e in ops) * 1e-9
    # overlapping and repeated intervals count once
    assert trace.union_seconds(tr.ops + tr.ops, lo, hi) == pytest.approx(busy)
    E = trace.Ev
    assert trace.union_seconds([E("a", 0, 10), E("b", 5, 20), E("c", 30, 40)],
                               0, 100) == pytest.approx(30e-9)
    assert trace.union_seconds([E("a", 0, 10)], 5, 100) == pytest.approx(5e-9)


def test_modules_matched_by_name(side, tr):
    names = {trace.module_name(e) for e in tr.within(tr.modules)}
    assert {"jit__decode", "jit__chunk"} <= names
    n = lambda kind: sum(1 for c in side["calls"] if c["kind"] == kind)
    runs = lambda name: sum(1 for e in tr.within(tr.modules)
                            if trace.module_name(e) == name)
    assert runs("jit__decode") == n("decode") > 0
    assert runs("jit__chunk") == n("chunk") > 0


def test_kernel_runs_found_and_counted(side, tr):
    spec = model.load_spec("qwen3-4b", side["hf"])
    seg = [c for c in side["calls"] if c["kind"] in ("decode", "chunk")
           and side["passes"][c["tier"]] > 0]
    kernels = [e for e in tr.within(tr.ops) if trace.is_kernel(e)]
    assert seg and kernels
    assert len(kernels) == counts.kernels_per_forward(spec) * len(seg)


def test_readers_give_what_the_run_printed(side, tr):
    sl = make_slice(side, tr)
    assert set(side["values"]) == set(READERS)
    for name in READERS:
        assert harness.read_metric(name, sl) == pytest.approx(
            side["values"][name]), name


@pytest.mark.parametrize("name", READERS)
def test_missing_source_is_nothing_not_zero(side, tr, name):
    empty = dataclasses.replace(tr, ops=[], modules=[])
    sl = make_slice(side, empty, busy_s=0.0, calls=[],
                    stats={k: dict.fromkeys(v, 0)
                           for k, v in side["stats"].items()})
    assert harness.read_metric(name, sl) is None


def test_kernel_count_mismatch_raises(side, tr):
    lost = dataclasses.replace(tr, ops=[e for i, e in enumerate(tr.ops)
                                        if not (trace.is_kernel(e) and i % 2)])
    with pytest.raises(trace.TraceError):
        harness.read_metric("afpm_matmul_roofline", make_slice(side, lost))


def test_breakdown_lists_ops_and_gaps(tr):
    b = trace.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"] + b["idle_gaps"])
