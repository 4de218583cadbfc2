"""A later change adds a configuration, a traffic mix and a per-layer metric
as new files and new entries only; the harness finds each by its name."""
import hashlib
import json
import os

from bench import harness
from bench.run import run_cell
from conftest import TINY_LM, write_json


def _digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_config_mix_and_metric_by_name(tiny_root):
    before = _digest(tiny_root)
    b = os.path.join(tiny_root, "bench")
    with open(os.path.join(b, "configs", "qwen1.5-0.5b.json")) as f:
        conf = json.load(f)
    conf.update(TINY_LM, name="tiny-lm", num_hidden_layers=1)
    write_json(os.path.join(b, "configs", "tiny-lm.json"), conf)
    with open(os.path.join(b, "traffic", "chat.json")) as f:
        mix = json.load(f)
    mix["rate_per_s"] = 1.0
    write_json(os.path.join(b, "traffic", "tiny_chat.json"), mix)
    with open(os.path.join(b, "metrics", "decode_tasks.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.tasks_of('decode')))\n")
    bm_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(bm_path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-lm", "source": "test", "file": "bench/configs/tiny-lm.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny.chat", "config": "tiny-lm", "traffic": "tiny_chat",
                            "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "decode_tasks", "unit": "tasks", "better": "higher",
                            "source": "program_span", "layer": "model host",
                            "moves": "itl_p95_s", "workloads": ["tiny.chat"]})
    for m in bm["end_to_end"]:
        if "workloads" in m and m["name"] in ("ttft_p95_s", "itl_p95_s"):
            m["workloads"].append("tiny.chat")
    write_json(bm_path, bm)
    after = _digest(tiny_root)
    assert all(after[p] == h for p, h in before.items()), "an existing file changed"

    cell = harness.find_cell(harness.benchmark(tiny_root), "tiny.chat", b)
    assert cell.config["num_hidden_layers"] == 1 and cell.traffic["rate_per_s"] == 1.0
    assert [m["name"] for m in cell.per_layer] == ["decode_tasks"]
    assert harness.metric_reader("decode_tasks", b) is not None

    out = run_cell("tiny.chat", 5, 3.0, True, require_chip=False, root=tiny_root)
    res = out["result"]
    assert res["correct"] is True
    assert res["metrics"]["decode_tasks"]["value"] > 0
    out = run_cell("tiny.chat", 5, 3.0, False, require_chip=False, root=tiny_root)
    assert set(out["result"]["metrics"]) == {"ttft_p95_s", "itl_p95_s", "setup_s"}
