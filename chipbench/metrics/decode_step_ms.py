"""Mean device time of a decode step, ms: the ``jit__decode`` program runs
in the slice (every lane's), summed and divided by their count."""
from chipbench.trace import module_name


def read(sl):
    runs = [e for e in sl.trace.within(sl.trace.modules)
            if module_name(e) == "jit__decode"]
    if not runs:
        return None
    return 1e-6 * sum(e.dur for e in runs) / len(runs)
