"""The arithmetic over the program's spans, on hand-built spans and gaps, and
every span metric of every cell read from a tiny CPU run with spans on."""
import pytest

from bench import harness, spans
from bench.run import run_cell
from bench.tools.spans import SPAN_METRICS, span_notes, with_spans
from repro.core.metrics import Span


def test_idle_gaps_are_the_complement_of_busy():
    assert spans.idle_gaps([(1.0, 2.0), (3.0, 4.0)], 0.0, 5.0) == [
        (0.0, 1.0), (2.0, 3.0), (4.0, 5.0)]
    assert spans.idle_gaps([(0.0, 5.0)], 0.0, 5.0) == []


def test_idle_goes_to_the_latest_started_open_span():
    gaps = [(0.0, 10.0), (20.0, 30.0)]
    hand = [
        (1.0, 9.0, "outer"),        # on one thread ...
        (2.0, 4.0, "inner"),        # ... with a child inside it
        (3.0, 6.0, "other"),        # another thread's span, started later
        (15.0, 25.0, "straddles"),  # open across the second gap's start
        (22.0, 23.0, "nested"),
    ]
    by = spans.idle_by_span(gaps, hand)
    assert by == pytest.approx({
        None: 1.0 + 1.0 + 5.0,      # 0-1, 9-10, 25-30
        "outer": 4.0,               # 1-2 and 6-9 (2-3 is inner's, 3-6 other's)
        "inner": 1.0,               # 2-3
        "other": 3.0,               # 3-6: started after inner
        "straddles": 4.0,           # 20-22, 23-25
        "nested": 1.0,
    })
    assert sum(by.values()) == pytest.approx(20.0)
    assert spans.idle_by_span(gaps, []) == {None: 20.0}


def _run(span_list, window=(0.0, 10.0)):
    run = harness.Run(cell="x", window=window)
    run.spans = span_list
    return run


def test_span_medians_counters_and_nothing_to_read():
    ms = 1_000_000
    lead = [Span("serving.coalesce_lead", "t", None, 1, i * ms, i * ms + k * ms)
            for i, k in ((1, 1), (2, 3), (3, 2), (11_000, 9))]  # the last after the window
    run = _run(lead)
    assert spans.span_median_ms(run, "serving.coalesce_lead") == pytest.approx(2.0)
    assert spans.span_median_ms(_run([]), "serving.coalesce_lead") is None
    bare = harness.Run(cell="x")                  # a run with no spans at all
    assert spans.span_median_ms(bare, "x") is None
    assert spans.idle_unexplained_pct(bare) is None
    run.counters_before = {"counters": {"serving.window_full": 3}}
    run.counters_after = {"counters": {"serving.window_full": 10,
                                       "serving.window_expired": 4}}
    assert spans.counter_delta(run, "serving.window_full") == 7
    assert spans.counter_delta(run, "serving.window_expired") == 4
    assert spans.counter_delta(run, "serving.no_such") is None
    full = harness.metric_reader("window_full_pct.decode")
    assert full(run) == pytest.approx(100 * 7 / 11)
    assert full(bare) is None
    # a counter the window never moved need not exist at all
    del run.counters_after["counters"]["serving.window_expired"]
    assert full(run) == pytest.approx(100.0)


@pytest.mark.parametrize("cell", ["serve.chat", "fn.short", "fn.frames"])
def test_span_metrics_read_in_a_traced_run(tiny_root, interpret_kernels, monkeypatch, cell):
    monkeypatch.setattr(harness, "find_cell", harness.find_cell)
    monkeypatch.setattr(harness, "driver", harness.driver)
    runs = []
    with_spans(harness, runs)
    out = run_cell(cell, 2**33 + 11, 3.0, True, require_chip=False, root=tiny_root)
    res = out["result"]
    assert res["correct"] is True and res["failed"] == 0
    assert runs and runs[-1].spans
    notes = span_notes(runs[-1])
    assert notes[0].startswith("spans in the window") and "service.submit: " in notes[0]
    for name, _, c in SPAN_METRICS:
        if c != cell:
            continue
        if name.startswith("idle_unexplained_pct."):
            # a share of the idle time between the CPU's operations
            assert 0 <= res["metrics"][name]["value"] <= 100, name
        else:
            assert res["metrics"][name]["value"] > 0, name
