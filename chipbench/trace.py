"""Read a ``jax.profiler`` trace of one slice of the window.

The profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it.  A TPU plane (``/device:TPU:<n>``) carries an ``XLA Ops`` line (every
operation the chip ran, Pallas kernels as ``tpu_custom_call`` operations)
and an ``XLA Modules`` line (every program run, named after its jitted
function: ``jit__decode``, ``jit__chunk``).  The host plane carries the
benchmark's ``TraceAnnotation`` spans on the thread that made them, on the
same clock (only those are kept: :func:`is_span`).  The slice is the
``chipbench.slice`` span.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os

SLICE = "chipbench.slice"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("engine.", "generator.")
SPAN_SUFFIXES = (".prefill", ".decode", ".retire")


def is_span(name: str) -> bool:
    """One of the benchmark's own host spans (``serve.py``, ``harness.py``)."""
    return (name == SLICE or name.startswith(SPAN_PREFIXES)
            or name.endswith(SPAN_SUFFIXES))


def options():
    """Profiler options for a slice: the host's annotations and runtime
    events, no Python call tracing (it multiplies the trace and slows the
    host it measures)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


class TraceError(RuntimeError):
    """The trace lacks what a metric reads."""


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    t0: float          # ns, on the trace's clock
    t1: float
    stats: tuple = ()  # ((key, value), ...)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    def stat(self, key, default=None):
        return dict(self.stats).get(key, default)


@dataclasses.dataclass
class Trace:
    ops: list          # device operations of the first TPU, sorted
    modules: list      # device program runs of the first TPU, sorted
    host: list         # benchmark spans on the host, sorted
    slice: tuple       # (t0, t1) of the slice span, ns

    def within(self, evs):
        a, b = self.slice
        return [e for e in evs if e.t0 >= a and e.t1 <= b]


def _events(line) -> list:
    out = []
    for e in line.events:
        try:
            stats = tuple((str(k), v) for k, v in e.stats)
        except Exception:  # noqa: BLE001 - stats of odd types are not needed
            stats = ()
        out.append(Ev(e.name, float(e.start_ns),
                      float(e.start_ns) + float(e.duration_ns), stats))
    return out


def from_profile(pd) -> Trace:
    """Reduce a ``ProfileData`` to the lines the metrics read."""
    tpus = sorted((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)
    ops, modules = [], []
    for p in tpus:   # the first chip's plane that holds operations
        lines = {ln.name: ln for ln in p.lines}
        if OPS_LINE in lines:
            ops = sorted(_events(lines[OPS_LINE]), key=lambda e: e.t0)
            if MODULES_LINE in lines:
                modules = sorted(_events(lines[MODULES_LINE]),
                                 key=lambda e: e.t0)
            break
    host = []
    for p in pd.planes:
        if not p.name.startswith("/host:CPU"):
            continue
        for ln in p.lines:
            host.extend(e for e in _events(ln) if is_span(e.name))
    host.sort(key=lambda e: e.t0)
    sl = [e for e in host if e.name == SLICE]
    if not sl:
        raise TraceError(f"the trace has no {SLICE!r} span")
    return Trace(ops=ops, modules=modules, host=host,
                 slice=(sl[0].t0, sl[0].t1))


def load(trace_dir: str) -> Trace:
    """The trace the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise TraceError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    return from_profile(ProfileData.from_file(paths[0]))


def load_bytes(path: str) -> Trace:
    """A recorded trace (``.xplane.pb``, optionally gzipped)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    return from_profile(ProfileData.from_serialized_xspace(raw))


def union_seconds(evs, lo: float, hi: float) -> float:
    """Length of the union of the events' intervals clipped to
    ``[lo, hi]`` (ns in, seconds out)."""
    spans = sorted((max(e.t0, lo), min(e.t1, hi)) for e in evs
                   if e.t1 > lo and e.t0 < hi)
    total, cur0, cur1 = 0.0, None, None
    for a, b in spans:
        if cur1 is None or a > cur1:
            if cur1 is not None:
                total += cur1 - cur0
            cur0, cur1 = a, b
        else:
            cur1 = max(cur1, b)
    if cur1 is not None:
        total += cur1 - cur0
    return total * 1e-9


def gaps(evs, lo: float, hi: float) -> list:
    """Idle intervals of the device inside ``[lo, hi]``: (t0, t1) ns."""
    out, t = [], lo
    for e in sorted(evs, key=lambda e: e.t0):
        if e.t1 <= lo or e.t0 >= hi:
            continue
        if e.t0 > t:
            out.append((t, e.t0))
        t = max(t, e.t1)
    if t < hi:
        out.append((t, hi))
    return out


def is_kernel(e: Ev) -> bool:
    """A Pallas kernel run: a ``tpu_custom_call`` operation."""
    return "tpu_custom_call" in (e.name + str(e.stat("hlo_category", ""))
                                 + str(e.stat("long_name", "")))


def op_name(e: Ev) -> str:
    """A device operation's short name: a kernel by its output shape, any
    other operation by its HLO instruction name."""
    name, _, rest = e.name.partition(" = ")
    if is_kernel(e):
        return f"tpu_custom_call {rest.split(' ')[0]}"
    return name


def is_container(e: Ev) -> bool:
    """A ``while``/``conditional``/``call`` operation, whose run encloses
    the operations of its body."""
    return any(f" {k}(" in e.name for k in ("while", "conditional", "call"))


def module_name(e: Ev) -> str:
    """``jit__decode(123)`` -> ``jit__decode``."""
    return e.name.split("(")[0].strip()


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most time in the slice (summed by
    :func:`op_name`; loops and calls, which enclose their bodies, left
    out), and the idle gaps summed by the innermost benchmark span open
    over each gap's middle."""
    lo, hi = tr.slice
    ops = tr.within(tr.ops)
    by_op = {}
    for e in ops:
        if not is_container(e):
            k = op_name(e)
            by_op[k] = by_op.get(k, 0.0) + e.dur * 1e-9
    spans = [e for e in tr.host if e.name != SLICE]
    # the innermost benchmark span open over a gap's middle: what the host
    # was doing while the device idled
    by_span = {}
    for a, b in gaps(ops, lo, hi):
        mid = (a + b) / 2
        inner = [e for e in spans if e.t0 <= mid <= e.t1]
        name = (min(inner, key=lambda e: e.dur).name if inner
                else "outside any span")
        by_span[name] = by_span.get(name, 0.0) + (b - a) * 1e-9
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_op), "idle_gaps": rank(by_span)}
