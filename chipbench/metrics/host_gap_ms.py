"""Device idle time per decode step that the engine's own host work
leaves, ms: the part of the slice in which the first chip runs no
operation that falls inside the union of the engine's host spans
(``engine.admit``, ``.prefill``, ``.batch``, ``.launch``, ``.sync``,
``.land``, ``.retire``; nested spans count once), over the decode steps
the lanes ran in the slice (``TierStats.n_decode_steps``).  A program
without these spans reads 0.0."""
from chipbench.trace import gaps

SPANS = {"engine.admit", "engine.prefill", "engine.batch", "engine.launch",
         "engine.sync", "engine.land", "engine.retire"}


def merged(evs) -> list:
    """The union of the events' intervals as sorted disjoint (t0, t1)."""
    out = []
    for e in sorted(evs, key=lambda e: e.t0):
        if out and e.t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.t1)
        else:
            out.append([e.t0, e.t1])
    return out


def overlap_s(a, b) -> float:
    """Length shared by two sorted lists of disjoint intervals (ns in,
    seconds out)."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total * 1e-9


def read(sl):
    steps = sum(s["n_decode_steps"] for s in sl.stats.values())
    if not steps:
        return None
    lo, hi = sl.trace.slice
    spans = merged(e for e in sl.trace.host if e.name in SPANS)
    return 1e3 * overlap_s(gaps(sl.trace.ops, lo, hi), spans) / steps
