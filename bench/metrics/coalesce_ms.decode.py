"""Median time a DecodeCoalescer leader waited for peers before its batched
step (the program's serving.coalesce_lead spans), ms."""
from bench.spans import span_median_ms


def read(run):
    return span_median_ms(run, "serving.coalesce_lead")
