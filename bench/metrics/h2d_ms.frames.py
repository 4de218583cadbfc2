"""Median per task of the worker.dispatch span: the jitted call until it
returns, which moves the frames to the device and enqueues the program, ms."""
from bench.spans import per_task_ms


def read(run):
    return per_task_ms(run, "task", ["worker.dispatch"])
