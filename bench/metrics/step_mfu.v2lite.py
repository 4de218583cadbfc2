"""Model FLOPs of the tokens the traced decode steps served (the dense
layer, attention projections, shared experts, the routed pairs on held
experts at the window's share of them, the head, attention to each token's
position), over those steps' device time at the chip's bf16 peak, %."""
from bench import flops_mla_moe as fm
from bench.readers import decode_steps
from bench.spans import counter_delta


def read(run):
    steps = decode_steps(run)
    held = counter_delta(run, "serving.moe_assign_held.decode")
    every = counter_delta(run, "serving.moe_assign_all.decode")
    if not steps or run.sizes is None or "R" not in run.sizes or not every:
        return None
    pairs = fm.held_pairs_per_token(run.sizes, held, every)
    work = sum(fm.decode_token_flops(run.sizes, n, pairs) for _, kv in steps for n in kv)
    t = sum(m.dur for m, _ in steps)
    return 100.0 * work / (t * run.peaks["bf16_flops"]) if t > 0 else None
