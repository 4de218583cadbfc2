"""The client thread's own work per task, ms: the median per task of its
service.submit span, plus the median service.fetch span (the harness fetches
the result, not the future, so fetch spans carry no task id)."""
from bench.spans import per_task_ms, span_median_ms


def read(run):
    submit = per_task_ms(run, "task", ["service.submit"])
    fetch = span_median_ms(run, "service.fetch")
    if submit is None or fetch is None:
        return None
    return submit + fetch
