"""The reader of the program's own host spans (``host_gap_ms``) on
synthetic slices whose device operations and host spans are placed by
hand (ns on the trace's clock)."""
import pytest

from chipbench import harness, trace

E = trace.Ev


def make_slice(modules=(), host=(), stats=None, lo=0.0, hi=1000.0):
    tr = trace.Trace(ops=list(modules), modules=list(modules),
                     host=sorted(host, key=lambda e: e.t0), slice=(lo, hi))
    if stats is None:
        stats = {"bulk": {"n_decode_steps": 2}}
    return harness.Slice(spec=None, mix={}, peaks={},
                         window_s=(hi - lo) * 1e-9,
                         busy_s=trace.union_seconds(modules, lo, hi),
                         trace=tr, calls=[], stats=stats, passes={})


def read(name, sl):
    return harness.read_metric(name, sl)


# the device runs [0, 400) and [600, 1000): one 200 ns idle gap
PROGRAMS = [E("jit__decode(1)", 0, 400), E("jit__decode(1)", 600, 1000)]


def test_host_gap_counts_the_idle_part_a_span_covers():
    # a span over half the gap, and one over a busy stretch only
    host = [E(trace.SLICE, 0, 1000), E("engine.sync", 100, 400),
            E("engine.land", 500, 700)]
    # 100 ns of idle under engine.land, over 2 decode steps
    assert read("host_gap_ms", make_slice(PROGRAMS, host)) == \
        pytest.approx(1e3 * 100e-9 / 2)


def test_host_gap_counts_nested_spans_once():
    host = [E(trace.SLICE, 0, 1000), E("engine.land", 400, 600),
            E("engine.retire", 450, 550), E("engine.sync", 350, 450)]
    assert read("host_gap_ms", make_slice(PROGRAMS, host)) == \
        pytest.approx(1e3 * 200e-9 / 2)


def test_host_gap_ignores_the_benchmarks_own_spans():
    # engine.step and a lane's decode span are the benchmark's, not the
    # program's: a program without spans reads 0.0, not None
    host = [E(trace.SLICE, 0, 1000), E("engine.step", 0, 1000),
            E("bulk.decode", 300, 700)]
    assert read("host_gap_ms", make_slice(PROGRAMS, host)) == 0.0


def test_host_gap_counts_only_the_slice():
    # a span that starts before the slice: only its part in the slice
    # counts, and the device idles from the slice's start to 100 ns
    programs = [E("jit__decode(1)", 100, 400), PROGRAMS[1]]
    host = [E(trace.SLICE, 0, 1000), E("engine.launch", -500, 50)]
    assert read("host_gap_ms", make_slice(programs, host)) == \
        pytest.approx(1e3 * 50e-9 / 2)


def test_a_slice_without_decode_reads_nothing():
    host = [E(trace.SLICE, 0, 1000), E("engine.land", 0, 1000)]
    sl = make_slice(PROGRAMS, host, {"bulk": {"n_decode_steps": 0}})
    assert read("host_gap_ms", sl) is None
