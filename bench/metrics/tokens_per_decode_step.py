"""Mean number of sessions the DecodeCoalescer merged into one batched decode
step over the window (the program's serving.merged_per_step histogram)."""
from bench.readers import histogram_mean


def read(run):
    return histogram_mean(run, "serving.merged_per_step")
