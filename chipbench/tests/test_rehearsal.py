"""Each cell end to end on the CPU at a reduced width, and a cell added by
files and entries alone.

Interpret-mode Pallas makes these slow (about a minute per lane); run them
explicitly: ``PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest
chipbench/tests``.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.tests.helpers import ROOT, bench, rehearse

CELLS = [c["name"] for c in bench()["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def assert_line(stdout: str, cell: str, section: str):
    line = json.loads(stdout.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(line)
    assert list(line)[-1] == "checks"
    want = {m["name"] for m in harness.declared(bench(), cell, section)}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    return line


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(cell):
    result, out, err = rehearse(cell)
    line = assert_line(out, cell, "end_to_end")
    assert line["correct"] is True, err
    assert line["checks"]["tokens_compared"]["value"] >= 100
    assert "in the window 0 compiles" in err
    assert err.strip().splitlines()[-1].startswith("check logit_gap_max_sd")


def test_traced_run_without_a_chip_trace_reports_nothing():
    """A traced run whose trace holds no TPU operations cannot read its
    device metrics: it raises, so ``run.py`` exits non-zero and prints no
    result line, never a 0 in a metric's place."""
    with pytest.raises(harness.RunError, match="found nothing to read"):
        rehearse("qwen3-4b.bulk-backlog", traced=True,
                 backend="xla")


ADDED_METRIC = '''"""Prefill chunks the slice ran (a counter)."""


def read(sl):
    n = sum(1 for c in sl.calls if c.kind == "chunk")
    return n or None
'''

HELPER = '''
import json, sys, time
sys.path[:0] = [".", "{src}"]
from chipbench import harness, run
from chipbench.tests.helpers import TINY
b = json.load(open("BENCHMARK.json"))
cell = {{c["name"]: c for c in b["workloads"]}}["tiny.mini-chat"]
hf = json.load(open("chipbench/configs/tiny.json"))
for traced in (False, True):
    r = harness.run_cell(b, cell, 12345, 2.0, traced, t_start=time.monotonic(),
                         device={{"platform": "cpu", "kind": "TPU v5 lite", "count": 1}},
                         hf=hf, backend="xla")
    run.report(r)
'''


def test_a_cell_config_mix_and_metric_added_by_files(tmp_path):
    """A later PR adds a configuration, a traffic mix, a cell and a
    per-layer metric by new files and new entries only; the harness finds
    them by name."""
    dst = tmp_path / "repo"
    shutil.copytree(ROOT / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    b = bench()
    hf = json.loads((ROOT / "chipbench/configs/qwen3-4b.json").read_text())
    from chipbench.tests.helpers import TINY
    hf.update(TINY)
    (dst / "chipbench/configs/tiny.json").write_text(json.dumps(hf))
    mix = json.loads((ROOT / "chipbench/traffic/chat-mixed.json").read_text())
    mix["engine"].update(max_len=256, slots=2,
                         tiers=[{"name": "premium", "policy": "exact"}])
    mix["prompt_tokens"].update(median=64, min=16, max=128)
    mix["output_tokens"].update(median=32, min=8, max=64)
    mix["arrivals"].update(rate_per_s=4.0, preroll_s=1.0)
    (dst / "chipbench/traffic/mini-chat.json").write_text(json.dumps(mix))
    (dst / "chipbench/limits/tiny.mini-chat.json").write_text(json.dumps(
        {"requests": 8, "tokens_compared_min": 20, "logit_gap_max_sd": 0.7,
         "cpu_rehearsal_s": {"tiny": 2.0, "small": 20.0}}))
    (dst / "chipbench/metrics/chunks_in_slice.py").write_text(ADDED_METRIC)
    b["configs"].append({"name": "tiny", "source": "test", "file":
                         "chipbench/configs/tiny.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": "tiny.mini-chat", "config": "tiny",
                           "traffic": "mini-chat", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "chunks_in_slice", "unit": "chunks",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine", "moves": "tokens_per_s",
                           "workloads": ["tiny.mini-chat"]})
    for m in b["end_to_end"]:
        if "workloads" in m and m["name"] != "ttft_p95_ms":
            continue
        m.setdefault("workloads", [c["name"] for c in bench()["workloads"]])
        m["workloads"].append("tiny.mini-chat")
    (dst / "BENCHMARK.json").write_text(json.dumps(b))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run([sys.executable, "-c",
                        HELPER.format(src=ROOT / "src")],
                       cwd=dst, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    assert set(lines[0]["metrics"]) == {"tokens_per_s", "ttft_p95_ms",
                                        "setup_s"}
    assert set(lines[1]["metrics"]) == {"chunks_in_slice"}
    assert lines[1]["metrics"]["chunks_in_slice"]["value"] >= 1
    assert all(x["correct"] for x in lines)
    # the control and fault tests, unedited, take the added cell too
    env["PYTHONPATH"] = str(ROOT / "src")
    p = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p",
                        "no:cacheprovider", "-k", "mini-chat",
                        "chipbench/tests/test_control.py",
                        "chipbench/tests/test_faults.py"],
                       cwd=dst, env=env, capture_output=True, text=True,
                       timeout=1800)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "4 passed" in p.stdout, p.stdout[-3000:]
