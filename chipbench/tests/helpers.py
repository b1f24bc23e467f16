"""Drive a whole run of a cell on the CPU at a reduced width.

The harness's look for a chip lives in ``run.py``'s ``main``; these
helpers call what follows it, ``harness.run_cell``, with the configuration
cut to a few dozen widths (the traffic and engine settings as the cell
states them) and the Pallas kernels in interpret mode.
"""
from __future__ import annotations

import io
import json
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# widths cut so that a CPU runs a cell in a minute or two; the init
# spread follows the width, as initializer_range ~ hidden_size ** -0.5
# does at the published sizes
TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=512, initializer_range=0.125)
# deep enough that precision errors compound as they do at full size: the
# control and fault checks run here
SMALL = dict(hidden_size=128, intermediate_size=512, num_hidden_layers=8,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             vocab_size=2048, initializer_range=128 ** -0.5)
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def rehearsal_seconds(cell: str, size: str, root: Path = ROOT) -> float:
    """The window of a CPU rehearsal of ``cell`` at ``size`` (``tiny`` or
    ``small``), from the cell's limits file (``cpu_rehearsal_s``): long
    enough that several requests finish and, in an open loop, that
    requests fall due."""
    with open(root / "chipbench" / "limits" / f"{cell}.json") as f:
        return float(json.load(f)["cpu_rehearsal_s"][size])


def bench(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def tiny_config(name: str, root: Path = ROOT, sizes=TINY) -> dict:
    with open(root / "chipbench" / "configs" / f"{name}.json") as f:
        hf = json.load(f)
    hf.update(sizes)
    return hf


def rehearse(cell: str, *, seed: int = 2 ** 31 + 7, seconds=None,
             traced: bool = False, backend: str = "interpret", sizes=TINY,
             **kw):
    """One run of ``cell``; returns (result, stdout, stderr) as ``run.py``
    would print them."""
    from chipbench import harness, run

    b = bench()
    c = {x["name"]: x for x in b["workloads"]}[cell]
    log = io.StringIO()
    if seconds is None:
        seconds = rehearsal_seconds(cell, "tiny")
    result = harness.run_cell(b, c, seed, seconds, traced,
                              t_start=time.monotonic(), device=CPU,
                              hf=tiny_config(c["config"], sizes=sizes),
                              backend=backend,
                              log=log, **kw)
    out, err = io.StringIO(), io.StringIO()
    run.report(result, out, err)
    return result, out.getvalue(), log.getvalue() + err.getvalue()
