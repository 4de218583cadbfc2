"""The trace reduction, on a small trace recorded on a TPU v5e: a 2-layer
full-width qwen1.5-0.5b running two prefills (128 and 256 tokens), two slot
inserts and three batched decode steps over 4 slots. And where in the window
the traced slice is placed."""
import os
from types import SimpleNamespace

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


# ---------------------------------------------------------------- placement
def _schedule(traffic, seconds):
    """The requests a driver plans for the committed mix `traffic`, or None
    for a closed loop."""
    from bench import traffic as gen
    from bench.harness import BENCH_DIR, load_json

    mix = load_json(os.path.join(BENCH_DIR, "traffic", traffic + ".json"))
    plan = gen.sessions if mix["kind"] == "sessions" else gen.tasks
    return plan(mix, 2**33 + 7, seconds)


@pytest.mark.parametrize("seconds", [51.0, 3.0])
@pytest.mark.parametrize("traffic", ["chat", "doc", "frames", "short"])
def test_slice_starts_at_a_due_request(traffic, seconds):
    """The slice opens as a request is due, the first at or after the
    window's middle, and lies wholly inside the window."""
    from bench.harness import trace_offset
    from bench.run import TRACE_SECONDS

    dur = min(TRACE_SECONDS, seconds / 2)
    mid = (seconds - dur) / 2
    requests = _schedule(traffic, seconds)
    at = trace_offset(requests, seconds, dur)
    assert 0.0 <= at and at + dur <= seconds
    if requests is None:
        assert at == mid
        return
    assert at in {r.due for r in requests}
    fits = [r.due for r in requests if r.due + dur <= seconds]
    assert not [d for d in fits if mid <= d < at]           # none passed over
    assert at >= mid or not [d for d in fits if d >= mid]


@pytest.mark.parametrize("requests", [None, [], [SimpleNamespace(due=50.0)]])
def test_slice_without_a_fitting_request_takes_the_middle(requests):
    from bench.harness import trace_offset

    assert trace_offset(requests, 51.0, 3.0) == 24.0


def test_slice_falls_back_to_the_last_request_that_fits():
    from bench.harness import trace_offset

    reqs = [SimpleNamespace(due=d) for d in (1.0, 10.0, 49.0)]
    assert trace_offset(reqs, 51.0, 3.0) == 10.0


@pytest.mark.parametrize("step_s,old_empty", [(0.005, True), (0.030, True), (0.130, False)])
def test_doc_slice_holds_a_live_session_at_any_speed(step_s, old_empty):
    """serve.v2lite.doc's schedule against a program taking `step_s` a token
    after a 0.5 s prefill: the clock's slice [24, 27] is empty once a token
    takes under ~35 ms; the slice placed by the schedule holds the prefill
    and first decode step of the session due at its start."""
    from bench.harness import trace_offset
    from bench.run import TRACE_SECONDS

    seconds, prefill = 51.0, 0.5
    requests = _schedule("doc", seconds)
    live = [(r.due, r.due + prefill + r.out_len * step_s) for r in requests]

    def busy(a, b):
        return [s for s in live if s[0] < b and s[1] > a]

    dur = min(TRACE_SECONDS, seconds / 2)
    old = (seconds - dur) / 2
    assert (old, old + dur) == (24.0, 27.0)
    assert (not busy(old, old + dur)) == old_empty
    at = trace_offset(requests, seconds, dur)
    assert at == pytest.approx(28.34, abs=0.01)
    assert busy(at, at + dur)
    assert at + prefill + step_s <= at + dur
