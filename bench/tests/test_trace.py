"""The trace reduction, on a small trace recorded on a TPU v5e: a 2-layer
full-width qwen1.5-0.5b running two prefills (128 and 256 tokens), two slot
inserts and three batched decode steps over 4 slots."""
import os

import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "tpu_serving_2layer.xplane.pb")
# the monotonic clock read inside the annotation "bench_mark" when it was recorded
MARK_NS, END_NS = 48626647669, 48644598069


@pytest.fixture(scope="module")
def trace():
    return tr.load(DATA, MARK_NS, MARK_NS * 1e-9, END_NS * 1e-9, mark="bench_mark")


def test_programs_found_by_name(trace):
    names = [m.name for m in trace.modules]
    assert names.count("jit_decode_step") == 3
    assert names.count("jit_prefill") == 2
    assert names.count("jit_insert_sequence") == 2
    # the host clock: every program ran after the mark and before the end
    assert all(trace.t0 <= m.start and m.start + m.dur <= trace.t1 for m in trace.modules)


def test_kernels_by_program_and_shape(trace):
    pre = trace.kernels_in("jit_prefill")
    # one causal kernel per layer per prefill, (heads, positions, head_dim)
    assert sorted(k.shape for k in pre) == [(16, 128, 64)] * 2 + [(16, 256, 64)] * 2
    # decode: one kernel per slot per layer per step
    assert len(trace.kernels_in("jit_decode_step")) == 3 * 2 * 4


def test_busy_union_and_idle_share(trace):
    busy = trace.busy_intervals()
    assert all(a < b for a, b in busy)
    assert all(b1 <= a2 for (_, b1), (a2, _) in zip(busy, busy[1:]))
    # the union never exceeds the sum of the programs' spans, nor the window
    total = sum(m.dur for m in trace.modules)
    assert 0 < trace.busy_s() <= total + 1e-9
    assert 0.0 < trace.idle_share() < 1.0
    assert trace.busy_s() == pytest.approx(sum(b - a for a, b in busy))


def test_union_of_overlapping_ops():
    t = tr.Trace(t0=0.0, t1=10.0, ops=[
        tr.Op("a", 1.0, 2.0), tr.Op("b", 2.0, 2.0), tr.Op("c", 6.0, 1.0),
        tr.Op("d", 9.5, 2.0), tr.Op("e", -1.0, 1.5)])
    assert t.busy_intervals() == [(0.0, 0.5), (1.0, 4.0), (6.0, 7.0), (9.5, 10.0)]
    assert t.busy_s() == pytest.approx(5.0)
    assert t.idle_share() == pytest.approx(0.5)


def test_breakdown_lists(trace):
    ops = trace.top_ops(10)
    assert 0 < len(ops) <= 10
    assert all(not name.split("/")[-1].startswith("while") for name, _ in ops)
    gaps = trace.idle_gaps(10)
    assert 0 < len(gaps) <= 10
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_readers_on_recorded_trace(trace):
    """The per-layer readers on the recorded programs: shares of a peak in
    (0, 100], decode work given to the steps that served it."""
    from types import SimpleNamespace

    from bench import readers
    from bench.harness import Run, TaskRec
    from bench.peaks import peaks_for
    from bench.reference.dense_lm import sizes

    hp = {"hidden_size": 1024, "intermediate_size": 2816, "num_hidden_layers": 2,
          "num_attention_heads": 16, "num_key_value_heads": 16, "vocab_size": 151936}
    steps = [m for m in trace.modules if m.name == "jit_decode_step"]

    class Done:
        def __init__(self, end):
            self.timestamps = SimpleNamespace(exec_end=end, result_ready=end)

        def done(self):
            return True

        def exception(self, timeout=None):
            return None

    # the recorded steps served slots at positions 5, 130, 260 and 0
    tasks = [TaskRec("decode", Done(m.start + m.dur + 1e-4), {"n_tokens": n})
             for m in steps for n in (6, 131, 261, 1)]
    run = Run(cell="t", trace=trace, tasks=tasks, sizes=sizes(hp),
              peaks=peaks_for("TPU v5 lite"))
    got = readers.decode_steps(run)
    assert [sorted(kv) for _, kv in got] == [[1, 6, 131, 261]] * 3
    for read in (readers.step_mfu_decode, readers.decode_attention_roofline):
        v = read(run)
        assert v is not None and 0.0 < v <= 100.0, (read.__name__, v)
