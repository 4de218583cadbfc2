"""Every cell end to end on the CPU at a tiny size, the attention kernels in
Pallas interpret mode: traffic, the window, the metric arithmetic and the
comparison that decides `correct`. What prints a result is only the chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.run import run_cell
from conftest import ROOT

CELLS = ["serve.chat", "fn.short", "fn.frames"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct(tiny_root, interpret_kernels, cell, trace):
    out = run_cell(cell, 2**33 + 7, 3.0, trace, require_chip=False, root=tiny_root)
    res = out["result"]
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    wanted = {m["name"] for m in bm["end_to_end"] if cell in m.get("workloads", [cell])}
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert "breakdown" in res
        host_read = {m["name"] for m in bm["per_layer"] if cell in m["workloads"]
                     and m["source"] != "device_trace"}
        assert host_read <= set(res["metrics"])
    else:
        assert set(res["metrics"]) == wanted
    assert any(n.startswith("compiles inside the window: 0") for n in out["notes"])


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "fn.short", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    # a checkout holding only BENCHMARK.json and bench/: nothing to run
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in env.items() if k not in ("PYTHONPATH",)}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "fn.short", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
