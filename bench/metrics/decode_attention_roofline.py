"""The decode attention kernel's least time for the keys and values each
served token needs (to its own position), over the kernel's device time, %."""
from bench.readers import decode_attention_roofline


def read(run):
    return decode_attention_roofline(run)
