"""Median time a task task spends in the fabric's host path (service,
forwarder, endpoint, result return): total minus t_e of its timestamp trail, ms."""
from bench.readers import fabric_ms


def read(run):
    return fabric_ms(run, "task")
