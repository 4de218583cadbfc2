"""Model FLOPs of the tokens the traced decode steps served, over those
steps' device time at the chip's bf16 peak, %."""
from bench.readers import step_mfu_decode


def read(run):
    return step_mfu_decode(run)
