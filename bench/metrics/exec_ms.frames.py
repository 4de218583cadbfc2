"""Median t_e of a task: payload resolve and unpack, the jitted call, result
spill and repack, in the worker, ms."""
from bench.readers import exec_ms


def read(run):
    return exec_ms(run, "task")
