"""The rest of a run, with the timed path broken underneath, comes out not
correct: a token or an answer altered where it is produced; and the control
(the reference one precision lower in the program's place) fails the same
comparison. bench/tools/control.py reads the control on the chip."""
import json
import os

import numpy as np
import pytest

from bench import harness
from bench.run import run_cell
from conftest import write_json


def test_decode_token_altered(tiny_root, monkeypatch):
    from repro.serving import fabric

    orig = fabric.ModelHost.decode

    def altered(self, session, tokens):
        tok, migrated = orig(self, session, tokens)
        return (tok + self.cfg.vocab // 2) % self.cfg.vocab, migrated

    monkeypatch.setattr(fabric.ModelHost, "decode", altered)
    res = run_cell("serve.chat", 21, 3.0, False, require_chip=False, root=tiny_root)["result"]
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > res["checks"]["max_logit_gap"]["limit"]


def test_prefill_token_altered(tiny_root, monkeypatch):
    from repro.serving import fabric

    orig = fabric.ModelHost.prefill

    def altered(self, session, tokens):
        return (orig(self, session, tokens) + self.cfg.vocab // 2) % self.cfg.vocab

    monkeypatch.setattr(fabric.ModelHost, "prefill", altered)
    res = run_cell("serve.chat", 22, 3.0, False, require_chip=False, root=tiny_root)["result"]
    assert res["correct"] is False


@pytest.mark.parametrize("cell", ["fn.short", "fn.frames"])
def test_answer_altered(tiny_root, monkeypatch, cell):
    from repro.core import worker

    orig = worker._JaxExecutable.__call__

    def altered(self, payload):
        out = dict(orig(self, payload))
        k = sorted(out)[-1]
        out[k] = np.asarray(out[k]) + 1
        return out

    monkeypatch.setattr(worker._JaxExecutable, "__call__", altered)
    res = run_cell(cell, 23, 2.0, False, require_chip=False, root=tiny_root)["result"]
    assert res["correct"] is False


@pytest.mark.parametrize("cell", ["serve.chat", "fn.short", "fn.frames"])
def test_control_fails_its_limit(tiny_root, interpret_kernels, cell):
    """The control, put through the comparison that decides `correct` on the
    program's own sample, comes out not correct; the program, correct. The
    model is wider than the other tests' so that float8's error shows as it
    does at full width."""
    if cell == "serve.chat":
        path = os.path.join(tiny_root, "bench", "configs", "qwen1.5-0.5b.json")
        with open(path) as f:
            conf = json.load(f)
        conf.update(hidden_size=512, intermediate_size=1024, num_hidden_layers=4,
                    num_attention_heads=8, num_key_value_heads=8, vocab_size=4096)
        write_json(path, conf)
    bm = harness.benchmark(tiny_root)
    c = harness.find_cell(bm, cell, tiny_root + "/bench")
    drv = harness.driver(c.config["kind"], tiny_root + "/bench").Driver(c, 31, 3.0)
    drv.setup()
    drv.window(harness.Run(cell=cell))
    drv.free()
    prog, ctl = drv.check(), drv.control_check()
    assert prog["correct"] is True, prog["numbers"]
    assert ctl["correct"] is False, ctl["numbers"]
    assert any(v > lim for _, v, lim in ctl["numbers"]), ctl["numbers"]


def test_refused_submissions_are_failed_tasks(tiny_root, monkeypatch):
    """A fabric that refuses some submissions (no live endpoint) leaves the
    closed loop running: those tasks count as failed, the rest are checked."""
    from repro.core.service import FunctionService

    orig = FunctionService.run
    calls = {"n": 0}

    def flaky(self, *args, **kwargs):
        calls["n"] += 1
        if calls["n"] > 50 and calls["n"] % 7 == 0:   # after set-up
            raise RuntimeError("no live endpoints registered with the forwarder")
        return orig(self, *args, **kwargs)

    monkeypatch.setattr(FunctionService, "run", flaky)
    res = run_cell("fn.short", 24, 2.0, False, require_chip=False, root=tiny_root)["result"]
    assert res["correct"] is True
    assert 0 < res["failed"] < res["attempted"]
