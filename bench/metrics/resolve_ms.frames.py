"""Median per task of the data.resolve span: the worker materializing the
payload's DataRef leaves from the endpoint's cache, ms."""
from bench.spans import per_task_ms


def read(run):
    return per_task_ms(run, "task", ["data.resolve"])
