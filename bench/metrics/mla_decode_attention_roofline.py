"""The latent decode attention kernel's least time for the latent rows each
served token needs (to its own position, read once as key and value), over
the kernel's device time in the traced decode steps, %."""
from bench import flops_mla_moe as fm
from bench.flops import roofline_time
from bench.readers import DECODE, _kernels_during, decode_steps


def read(run):
    steps = decode_steps(run)
    if not steps or run.sizes is None or "R" not in run.sizes:
        return None
    least = spent = 0.0
    for m, kv in steps:
        ks = _kernels_during(run, m, DECODE)
        if not ks:
            continue
        least += roofline_time(fm.mla_decode_attention(run.sizes, kv), run.peaks)["seconds"]
        spent += sum(k.dur for k in ks)
    return 100.0 * least / spent if spent > 0 else None
