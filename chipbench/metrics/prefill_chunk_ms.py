"""Mean device time of a prefill chunk, ms: the ``jit__chunk`` program
runs in the slice, summed and divided by their count."""
from chipbench.trace import module_name


def read(sl):
    runs = [e for e in sl.trace.within(sl.trace.modules)
            if module_name(e) == "jit__chunk"]
    if not runs:
        return None
    return 1e-6 * sum(e.dur for e in runs) / len(runs)
