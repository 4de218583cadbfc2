"""Median per task of the data.spill and worker.repack spans: the result's
large leaves put in the store, then the serializer round trip, ms."""
from bench.spans import per_task_ms


def read(run):
    return per_task_ms(run, "task", ["data.spill", "worker.repack"])
